"""Device time per training step in the ``short_conv`` op (the two gates
and the taps of every gated short convolution; the projections around it
are ``mul`` ops and count as products), both directions and, where a layer
is recomputed, again, by the innermost ``pt.`` scope
(``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    joined = op_attribution.join(ctx)
    if not joined["ok"]:
        return None
    return sum(r["ms_per_step"] for r in joined["rows"]
               if r["op_type"] == "short_conv")
