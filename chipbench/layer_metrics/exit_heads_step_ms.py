"""Device time per training step of what reads a loop's passes: the events
whose OUTERMOST ``pt.<op>:<block>.<position>`` scope is an op of the global
block that stands after the program's last ``repeat`` op and before its
``backward`` op, both directions (the owner by the fusion rule, so an
optimizer update fused behind a head's weight gradient counts here, as it
counts as ``matmul`` in the op table).  For a looped decoder: the head
after every pass, the exit gates, the exit distribution and the weighted
loss.  Nothing where the program holds no ``repeat`` op."""
from chipbench.layer_metrics.looped_stack_step_ms import ms_per_step
from chipbench.lib import op_attribution


def compute(ctx):
    import paddle_tpu

    ops = paddle_tpu.default_main_program().global_block().ops
    loops = [i for i, op in enumerate(ops) if op.type == "repeat"]
    ends = [i for i, op in enumerate(ops) if op.type == "backward"]
    if not loops:
        return None
    after = {f"0.{i}" for i in range(loops[-1] + 1,
                                     ends[0] if ends else len(ops))}

    def owns(op_name):
        # the first scope that names an op: the executor's own (pt.scan)
        # stand outside it and name none
        instances = (m.group(2) for m in op_attribution.SCOPE.finditer(op_name)
                     if m.group(2))
        return next(instances, None) in after

    return ms_per_step(ctx, owns)
