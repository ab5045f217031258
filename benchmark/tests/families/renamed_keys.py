"""Test-only family: the ``llama`` block published under other key names
(``num_experts`` for ``num_local_experts``, ``ffn_hidden_size`` for
``intermediate_size``, ``num_kv_heads`` for ``num_key_value_heads``), as the
next families' own ``config.json`` files do. It is found because
``benchmark/tests`` is on ``PYTHONPATH`` (``harness/family.py``), maps its own
keys, and reuses the program's forward, the ``llama`` reference and the
``llama`` arithmetic: what a family of a new block would write itself.
"""

from families import llama

RENAMED = {"num_experts": "num_local_experts", "ffn_hidden_size": "intermediate_size", "num_kv_heads": "num_key_value_heads"}


def as_llama(doc: dict) -> dict:
    return {RENAMED.get(k, k): v for k, v in doc.items()}


def model_config(doc, n_layers=None):
    return llama.model_config(as_llama(doc), n_layers)


def numerics_sizes(doc):
    return llama.numerics_sizes(as_llama(doc))


program = llama.program
reference = llama.reference


def decode_step_bytes(doc, live_kv_tokens):
    return llama.decode_step_bytes(as_llama(doc), live_kv_tokens)


def prefill_flops(doc, n_tokens, mean_context, routed=True):
    return llama.prefill_flops(as_llama(doc), n_tokens, mean_context, routed)
