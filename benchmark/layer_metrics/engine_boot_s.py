"""host: seconds from the backend's spawn of the engine host process to the
engine ready to serve: ``boot.spawn_to_main_s`` (the spawn stamp in the
child's environment → ``engine_main.main``'s entry: interpreter, site, the
package's import) + ``boot.ready_s`` (``main``'s entry → the loader sets
ready). The largest over the engines; read once, the block is frozen."""

from harness import boot


def read(before, after, responses, trace, cell):
    def spawn_to_ready(b):
        if b.get("spawn_to_main_s") is None or b.get("ready_s") is None:
            return None
        return b["spawn_to_main_s"] + b["ready_s"]

    return boot.largest(after, spawn_to_ready)
