"""The readers of an engine's boot (``/metrics`` ``boot``,
``agentainer_tpu/utils/boot.py``) on hand-built documents: the sums, the
largest over a fleet's engines, and ``None`` wherever the block, a stamp or a
stage is missing (the parent's documents have no ``boot`` at all)."""

import pytest

from layer_metrics import (
    boot_cache_misses,
    boot_import_backend_s,
    boot_jit_s,
    boot_warmup_s,
    boot_weights_s,
    engine_boot_s,
)

READERS = [engine_boot_s, boot_import_backend_s, boot_weights_s, boot_warmup_s, boot_jit_s, boot_cache_misses]


def phase(total_s, self_s=None, n=1):
    return {"n": n, "self_s": total_s if self_s is None else self_s, "total_s": total_s}


def engine(spawn_to_main_s, ready_s, scale=1.0):
    return {
        "engine_load_s": 70.0 * scale,
        "boot": {
            "spawned_unix_ns": 1, "started_unix_ns": 2, "spawn_to_main_s": spawn_to_main_s, "ready_s": ready_s,
            "warm_boot": False,
            "phases": {
                "boot.import": phase(4.0 * scale, n=2),
                "boot.backend": phase(6.0 * scale),
                "boot.weights": phase(12.0 * scale),
                "boot.engine_init": phase(3.0 * scale),
                "boot.warmup": phase(50.0 * scale, self_s=0.1),
                "boot.warmup_serve": phase(30.0 * scale),
                "boot.prewarm_prefix": phase(1.0 * scale),
            },
            "stages": {},
            "compile_cache_at_ready": {
                "requests": 90, "hits": 70, "misses": round(20 * scale),
                "trace_s": 9.0 * scale, "lower_s": 5.0 * scale, "compile_s": 21.0 * scale, "retrieval_s": 8.0 * scale,
            },
            "first_dispatch_s": 0.5, "first_dispatch_replayed": False,
        },
    }


def read(reader, docs):
    return reader.read([], docs, [], None, {})


def test_the_sums_of_one_engine():
    docs = [engine(0.5, 77.0)]
    assert read(engine_boot_s, docs) == pytest.approx(77.5)
    assert read(boot_import_backend_s, docs) == pytest.approx(0.5 + 4.0 + 6.0)
    assert read(boot_weights_s, docs) == pytest.approx(12.0)
    assert read(boot_warmup_s, docs) == pytest.approx(50.0)  # total_s: its parts are inside it
    assert read(boot_jit_s, docs) == pytest.approx(9.0 + 5.0 + 21.0)  # retrieval_s is inside compile_s
    assert read(boot_cache_misses, docs) == 20.0


def test_a_fleet_reads_as_its_slowest_engine():
    docs = [engine(0.5, 77.0), engine(0.7, 90.0, scale=1.5), {"engine_load_s": 1.0}]
    assert read(engine_boot_s, docs) == pytest.approx(90.7)
    assert read(boot_import_backend_s, docs) == pytest.approx(0.7 + 6.0 + 9.0)
    assert read(boot_weights_s, docs) == pytest.approx(18.0)
    assert read(boot_warmup_s, docs) == pytest.approx(75.0)
    assert read(boot_jit_s, docs) == pytest.approx(52.5)
    assert read(boot_cache_misses, docs) == 30.0


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_no_boot_block_reads_none(reader):
    assert read(reader, [{"engine_load_s": 70.0, "compile_cache": {"misses": 3}}]) is None
    assert read(reader, []) is None


def test_what_an_engine_cannot_say_is_none_not_zero():
    unspawned = engine(None, 77.0)  # embedded: nobody stamped a spawn
    assert read(engine_boot_s, [unspawned]) is None and read(boot_import_backend_s, [unspawned]) is None
    assert read(boot_weights_s, [unspawned]) == pytest.approx(12.0)
    loading = engine(0.5, None)  # read before ready: the totals are not frozen yet
    loading["boot"]["compile_cache_at_ready"] = None
    assert read(engine_boot_s, [loading]) is None
    assert read(boot_jit_s, [loading]) is None and read(boot_cache_misses, [loading]) is None
    warm = engine(0.5, 20.0)  # a respawn that skipped its warm-up
    del warm["boot"]["phases"]["boot.warmup"], warm["boot"]["phases"]["boot.warmup_serve"]
    assert read(boot_warmup_s, [warm]) is None and read(engine_boot_s, [warm]) == pytest.approx(20.5)
