"""The analyzer analyzing itself: every ATP rule gets a must-flag and a
must-not-flag fixture, plus the baseline ratchet's full lifecycle
(freeze -> suppress -> new-violation fails -> fix leaves a stale entry ->
prune tightens). The fixtures are tiny synthetic repos under tmp_path so
the tests pin RULE semantics, not the real tree's current violation set
— that set lives in analysis/baseline.json and shifts as code is fixed.
"""

import json
import textwrap

from agentainer_tpu.analysis.framework import (
    Baseline,
    assign_fingerprints,
    collect_sources,
    load_baseline,
    prune_baseline,
    run_rules,
    save_baseline,
)
from agentainer_tpu.analysis.rules import (
    ALL_RULES,
    ExceptDiscipline,
    FailpointParity,
    HotPathHostSync,
    JitDispatchDiscipline,
    LockHoldDiscipline,
)


def _repo(tmp_path, files: dict[str, str]):
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return tmp_path


def _run(rule, tmp_path, roots=("pkg",)):
    violations, report = run_rules(
        [rule], roots=roots, repo_root=tmp_path, baseline=Baseline(entries={})
    )
    return violations


# ---------------------------------------------------------------------------
# ATP001


def test_atp001_flags_silent_blanket_except(tmp_path):
    root = _repo(tmp_path, {"pkg/m.py": """
        try:
            x = 1
        except Exception:
            pass
    """})
    v = _run(ExceptDiscipline(), root)
    assert len(v) == 1 and v[0].rule_id == "ATP001"


def test_atp001_accepts_reraise_log_and_count(tmp_path):
    root = _repo(tmp_path, {"pkg/m.py": """
        class C:
            def f(self):
                try:
                    x = 1
                except Exception:
                    raise
                try:
                    x = 2
                except Exception as e:
                    print("boom", e)
                try:
                    x = 3
                except Exception:
                    self.errors_total += 1
                try:
                    x = 4
                except ValueError:
                    pass  # narrowed: not a blanket except
    """})
    assert _run(ExceptDiscipline(), root) == []


# ---------------------------------------------------------------------------
# ATP002


def test_atp002_flags_host_sync_in_hot_function(tmp_path):
    root = _repo(tmp_path, {"agentainer_tpu/engine/llm.py": """
        import time

        class LLMEngine:
            def _decode_dispatch(self):
                time.sleep(0.5)

            def _cold_helper(self):
                time.sleep(0.5)  # not a hot-path function: allowed
    """})
    v = _run(HotPathHostSync(), root, roots=("agentainer_tpu",))
    assert len(v) == 1
    assert "time.sleep" in v[0].message and "_decode_dispatch" in v[0].message


def test_atp002_honors_atp_hot_marker(tmp_path):
    root = _repo(tmp_path, {"pkg/worker.py": """
        import numpy as np

        def tight_loop(xs):  # atp: hot
            return np.asarray(xs)

        def setup(xs):
            return np.asarray(xs)  # cold: allowed
    """})
    v = _run(HotPathHostSync(), root)
    assert len(v) == 1 and "tight_loop" in v[0].message


# ---------------------------------------------------------------------------
# ATP003


def test_atp003_flags_blocking_call_under_page_lock(tmp_path):
    root = _repo(tmp_path, {"pkg/engine.py": """
        import time, jax

        class E:
            def bad(self):
                with self._page_lock:
                    jax.block_until_ready(self.cache)

            def also_bad(self):
                with self._page_lock:
                    time.sleep(1)

            def good(self):
                with self._page_lock:
                    self.free.extend(self.quarantine)
                jax.block_until_ready(self.cache)

            def closure_is_fine(self):
                with self._page_lock:
                    def later():
                        time.sleep(1)  # defined, not run, under the lock
                    self.cb = later
    """})
    v = _run(LockHoldDiscipline(), root)
    assert len(v) == 2
    assert all(x.rule_id == "ATP003" for x in v)


def test_atp003_flags_await_under_lock(tmp_path):
    root = _repo(tmp_path, {"pkg/engine.py": """
        class E:
            async def bad(self):
                with self._page_lock:
                    await self.store.get("k")
    """})
    v = _run(LockHoldDiscipline(), root)
    assert any("await" in x.message for x in v)


# ---------------------------------------------------------------------------
# ATP004


def test_atp004_three_way_parity(tmp_path):
    root = _repo(tmp_path, {
        "pkg/faults.py": """
            CATALOG = frozenset({"store.get", "engine.prefill", "ghost.seam"})
        """,
        "pkg/store.py": """
            from . import faults
            def get(self):
                faults.fire("store.get")
            def rogue(self):
                faults.fire("store.unlisted")
        """,
        "docs/RESILIENCE.md": """
            ### Failpoint catalog

            | name | seam | armed effect |
            |------|------|--------------|
            | `store.get` | store | blip |
            | `engine.prefill` | engine | poisoned prefill |

            ### Arming
        """,
    })
    msgs = [v.message for v in _run(FailpointParity(), root)]
    assert any("store.unlisted" in m and "missing from faults.CATALOG" in m for m in msgs)
    assert any("ghost.seam" in m and "no fire()" in m for m in msgs)
    # engine.prefill is in CATALOG but nothing fires it
    assert any("engine.prefill" in m and "no fire()" in m for m in msgs)
    assert any("ghost.seam" in m and "RESILIENCE.md" in m for m in msgs)
    # most seam categories have no failpoint in this tiny fixture
    assert any("seam category" in m for m in msgs)


def test_atp004_real_repo_is_in_parity():
    violations, _ = run_rules([FailpointParity()], baseline=Baseline(entries={}))
    assert violations == [], [v.format() for v in violations]


# ---------------------------------------------------------------------------
# ATP005


def test_atp005_flags_inline_and_looped_jit(tmp_path):
    root = _repo(tmp_path, {"pkg/m.py": """
        import jax

        def bad_inline(f, x):
            return jax.jit(f)(x)

        def bad_loop(fns):
            out = []
            for f in fns:
                out.append(jax.jit(f))
            return out

        def good_builder(f):
            fn = jax.jit(f)
            return fn

        class E:
            def good_keyed_cache(self, b):
                fn = self._fns.get(b)
                if fn is None:
                    fn = self._fns[b] = jax.jit(lambda x: x * b)
                return fn
    """})
    v = _run(JitDispatchDiscipline(), root)
    lines = sorted(x.line for x in v)
    assert len(v) == 2, [x.format() for x in v]
    assert "per evaluation" in v[0].message or "per evaluation" in v[1].message
    assert any("loop" in x.message for x in v)
    del lines


# ---------------------------------------------------------------------------
# baseline ratchet


RATCHET_SRC = """
try:
    x = 1
except Exception:
    pass
"""

RATCHET_SRC_TWO = """
try:
    x = 1
except Exception:
    pass

try:
    y = 2
except BaseException:
    pass
"""


def test_ratchet_freezes_then_fails_new_then_prunes(tmp_path):
    root = _repo(tmp_path, {"pkg/m.py": RATCHET_SRC})
    bpath = tmp_path / "baseline.json"
    rule = ExceptDiscipline()

    # 1. freeze the pre-existing violation
    violations, report = run_rules([rule], roots=("pkg",), repo_root=root,
                                   baseline=Baseline(entries={}))
    assert len(report.new) == 1
    baseline = save_baseline(violations, Baseline(entries={}), path=bpath)
    entry = next(iter(baseline.entries.values()))
    assert entry["justification"]  # every frozen site carries a string

    # 2. frozen: the same violation no longer fails
    _, report = run_rules([rule], roots=("pkg",), repo_root=root, baseline=baseline)
    assert report.ok and len(report.baselined) == 1

    # 3. a NEW violation fails even with the old one frozen
    (root / "pkg" / "m.py").write_text(RATCHET_SRC_TWO)
    _, report = run_rules([rule], roots=("pkg",), repo_root=root, baseline=baseline)
    assert not report.ok
    assert len(report.new) == 1 and "BaseException" in report.new[0].snippet
    assert len(report.baselined) == 1

    # 4. fixing the original violation leaves a stale entry; prune drops it
    (root / "pkg" / "m.py").write_text("x = 1\n")
    violations, report = run_rules([rule], roots=("pkg",), repo_root=root,
                                   baseline=baseline)
    assert report.ok and len(report.stale) == 1
    dropped = prune_baseline(violations, baseline, path=bpath)
    assert dropped == 1
    assert json.loads(bpath.read_text())["entries"] == {}


def test_fingerprints_stable_across_line_drift(tmp_path):
    root = _repo(tmp_path, {"pkg/m.py": RATCHET_SRC})
    rule = ExceptDiscipline()
    v1 = _run(rule, root)
    # shift the violation down 40 lines; fingerprint must not move
    (root / "pkg" / "m.py").write_text("# pad\n" * 40 + RATCHET_SRC)
    v2 = _run(rule, root)
    assert v1[0].fingerprint == v2[0].fingerprint
    assert v1[0].line != v2[0].line


def test_identical_sites_get_distinct_fingerprints(tmp_path):
    root = _repo(tmp_path, {"pkg/m.py": RATCHET_SRC + RATCHET_SRC})
    v = _run(ExceptDiscipline(), root)
    assert len(v) == 2
    assert v[0].fingerprint != v[1].fingerprint


# ---------------------------------------------------------------------------
# the real tree: the checked-in baseline covers the current violation set


def test_repo_is_clean_under_checked_in_baseline():
    violations, report = run_rules(ALL_RULES, baseline=load_baseline())
    assert report.ok, "\n" + report.format()
    # and the ratchet has no dead weight at commit time
    assert not report.stale, "\n" + report.format()


def test_every_baseline_entry_is_justified():
    """--update-baseline stamps new entries with a pending marker; a
    human must replace it with the real reason before the entry counts
    as settled. No entry ships pending."""
    from agentainer_tpu.analysis.framework import PENDING_JUSTIFICATION

    base = load_baseline()
    pending = [
        f"{e['path']}:{e['line']}"
        for e in base.entries.values()
        if not e.get("justification") or e["justification"] == PENDING_JUSTIFICATION
    ]
    assert not pending, f"baseline entries without a real justification: {pending}"


def test_collect_sources_skips_pycache(tmp_path):
    root = _repo(tmp_path, {
        "pkg/m.py": "x = 1\n",
        "pkg/__pycache__/m.py": "syntax error here (\n",
    })
    mods = collect_sources(("pkg",), root)
    assert [m.path for m in mods] == ["pkg/m.py"]


def test_assign_fingerprints_orders_by_position():
    from agentainer_tpu.analysis.framework import Violation

    a = Violation("ATP001", "p.py", 10, "m", snippet="except Exception:")
    b = Violation("ATP001", "p.py", 50, "m", snippet="except Exception:")
    assign_fingerprints([b, a])  # order of the list must not matter
    fa, fb = a.fingerprint, b.fingerprint
    assign_fingerprints([a, b])
    assert (a.fingerprint, b.fingerprint) == (fa, fb)
    assert fa != fb
