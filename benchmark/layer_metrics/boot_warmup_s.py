"""model runner: seconds of ``boot.warmup``, every serve-path program run
once (bucket passes and decode ladder, snapshot slicers, prefix copies,
verify ladder, mixed steps: its child spans name them); ``None`` where a warm
boot skipped it. The largest over the engines."""

from harness import boot


def read(before, after, responses, trace, cell):
    return boot.largest(after, lambda b: boot.total_s(b, "boot.warmup"))
