"""Test-only family: the ``olmoe`` family's program against the ``llama``
family's plain reference (no QK-norm, gates renormalised over the chosen k).
The numerics child has to fail it: what is compared against is the family's
own reference file."""

from families.llama import reference as _llama_reference
from families.olmoe import REHEARSAL_WIDTHS, model_config, numerics_sizes, program  # noqa: F401


def reference(params, cfg):
    layers = {k: v for k, v in params["layers"].items() if k not in ("q_norm", "k_norm")}
    return _llama_reference({**params, "layers": layers}, cfg)
