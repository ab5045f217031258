"""Start-up hook the harness puts first on the daemon's ``PYTHONPATH``.

Only registry names reach the engine (``LLMEngine.create`` → ``get_config``)
and the daemon validates a deploy against the same registry, so a benchmark
configuration that is not in ``models/configs.py`` has to be registered in
both processes. The engine host inherits the daemon's environment, so this
file runs in each: it reads the configuration file named in
``ATPU_BENCH_CONFIG``, asks the file's family (``harness/family.py``) for the
program's ``ModelConfig`` and calls the program's public ``register()``. It
imports nothing heavy (``models.configs`` is a dataclass and a dict; a family
module imports JAX only inside the functions of the numerics check) and
edits no program file. A model config accepted from a file by the program
itself would make it unnecessary (PERF.md, Open questions).
"""

import os
import sys


def _register() -> None:
    path = os.environ.get("ATPU_BENCH_CONFIG")
    if not path:
        return
    import json

    from agentainer_tpu.models.configs import register

    with open(path) as f:
        doc = json.load(f)
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, bench)  # for the family's import only: the program sees no benchmark module
    try:
        from harness.family import family_of

        cfg = family_of(doc).model_config(doc)
    finally:
        sys.path.remove(bench)
    register(cfg)


_register()
