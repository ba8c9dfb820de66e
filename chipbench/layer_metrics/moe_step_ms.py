"""Device time per training step in the ``moe`` op (router, sort and
gather, the experts' grouped products, weighting and gather back), both
directions, by the innermost ``pt.`` scope (``lib/op_attribution.py``; the
``moe.*`` scopes inside the op do not start with ``pt.``, so the whole op
is one owner)."""
from chipbench.lib import op_attribution


def compute(ctx):
    joined = op_attribution.join(ctx)
    if not joined["ok"]:
        return None
    return sum(r["ms_per_step"] for r in joined["rows"]
               if r["op_type"] == "moe")
