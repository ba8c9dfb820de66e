"""Device time per training step in the ``moe`` ops of a configuration that
holds one chip's share of its experts (router over all of them, sort and
gather of the rows held, their grouped products, weighting and gather
back), both directions and again where a layer is recomputed: the reading
of ``moe_step_ms``, under the name the share's cell lists."""
from chipbench.layer_metrics.moe_step_ms import compute  # noqa: F401
