"""host: seconds of a boot before the first byte of weights: the spawn →
``main`` stamp, ``boot.import`` (``import jax``, the engine's modules, the
compile cache turned on) and ``boot.backend`` (the TPU runtime coming up at
the first ``jax.devices()``). The largest over the engines."""

from harness import boot


def read(before, after, responses, trace, cell):
    def before_weights(b):
        stages = boot.total_s(b, "boot.import", "boot.backend")
        if b.get("spawn_to_main_s") is None or stages is None:
            return None
        return b["spawn_to_main_s"] + stages

    return boot.largest(after, before_weights)
