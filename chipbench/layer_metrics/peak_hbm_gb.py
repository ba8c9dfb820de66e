"""Peak device memory on the fullest chip, in GB (1e9 bytes): the larger
of the allocator's ``peak_bytes_in_use`` after the window and XLA's
``memory_analysis()`` of the window's program."""


def compute(ctx):
    return ctx.memory_peak_bytes / 1e9
