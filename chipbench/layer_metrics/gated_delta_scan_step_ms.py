"""Device time per training step in the ``gated_delta_scan`` op (the
chunked gated delta rule of every KDA layer: decays, the chunk's triangular
solve, the chunks' hand-over, the product inside a chunk and the output; the
projections, the filter, the gates and the norm around it are other ops),
both directions and, where a layer or one of the op's own stretches is
recomputed, again, by the innermost ``pt.`` scope
(``lib/op_attribution.py``; the ``delta.*`` scopes inside the op do not
start with ``pt.``, so the whole op is one owner)."""
from chipbench.lib import op_attribution


def compute(ctx):
    joined = op_attribution.join(ctx)
    if not joined["ok"]:
        return None
    found = [r["ms_per_step"] for r in joined["rows"]
             if r["op_type"] == "gated_delta_scan"]
    return sum(found) if found else None
