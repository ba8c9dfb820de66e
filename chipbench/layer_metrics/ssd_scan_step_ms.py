"""Device time per training step in the ``ssd_scan`` op (the chunked
state-space recurrence of every Mamba-2 layer: decays, the product inside a
chunk, the chunks' states, their hand-over and the output; the projections,
the filter, the gate and the norm around it are other ops), both directions
and, where a layer is recomputed, again, by the innermost ``pt.`` scope
(``lib/op_attribution.py``; the ``ssd.*`` scopes inside the op do not start
with ``pt.``, so the whole op is one owner)."""
from chipbench.lib import op_attribution


def compute(ctx):
    joined = op_attribution.join(ctx)
    if not joined["ok"]:
        return None
    return sum(r["ms_per_step"] for r in joined["rows"]
               if r["op_type"] == "ssd_scan")
