"""Device time per training step in ``flash_attention`` and ``rope``, both
directions, by the innermost ``pt.`` scope (``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    joined = op_attribution.join(ctx)
    if not joined["ok"]:
        return None
    return sum(r["ms_per_step"] for r in joined["rows"]
               if r["op_type"] in ("flash_attention", "rope"))
