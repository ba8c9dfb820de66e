"""Device time per training step in ``flash_attention`` and ``rope`` of a
configuration with grouped-query heads, both directions and again where a
layer is recomputed: the reading of ``attention_step_ms``, under the name
the grouped heads' cell lists."""
from chipbench.layer_metrics.attention_step_ms import compute  # noqa: F401
