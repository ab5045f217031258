"""The one seam between the harness and a model's block, found by name like
generators and layer metrics: a configuration file names its family under
``"family"`` (absent: ``llama``) and ``families/<name>.py`` is the only
module of the benchmark that knows that block. ``families/llama.py`` says
what a family answers. ``families`` is a directory of modules with no
package file, so that a second ``families/`` directory on ``PYTHONPATH``
(the test-only family under ``tests/``) is searched beside it.
"""

from __future__ import annotations

import importlib
import re


def family_of(doc: dict):
    """The family module of a configuration file's contents."""
    name = doc.get("family", "llama")
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"family {name!r} is not a module name")
    return importlib.import_module("families." + name)
