"""Building the native layer is safe between processes: whoever asks first
builds, the others wait and load what it made, and nobody opens a file that
is still being written (agentainer_tpu/native.py, native/Makefile).

Nothing here stands behind a skip on the library itself: these are the tests
that say so when it cannot be built where a compiler is."""

import json
import os
import shutil
import stat
import subprocess
import sys
import time
from pathlib import Path

import pytest

from agentainer_tpu import native

REPO = Path(__file__).resolve().parent.parent
needs_toolchain = pytest.mark.skipif(
    not (shutil.which("make") and shutil.which("g++")), reason="no make + g++ on PATH"
)

# what a process that starts on the check-out does: ask for the library
_CHILD = """
import json, sys
from pathlib import Path
from agentainer_tpu import native
native._NATIVE_DIR = Path(sys.argv[1])
print(json.dumps([native.available(), native.load_error()]))
"""


def _copy_of_native(tmp_path: Path) -> Path:
    dst = tmp_path / "native"
    shutil.copytree(REPO / "native", dst, ignore=shutil.ignore_patterns("build"))
    return dst


def _logging_compiler(tmp_path: Path, delay_s: float = 0.0) -> tuple[dict, Path]:
    """An environment whose ``CXX`` writes its arguments down (one line a
    call), waits, and then runs the real compiler."""
    log = tmp_path / "cxx.log"
    cxx = tmp_path / "cxx.sh"
    cxx.write_text(f'#!/bin/sh\necho "$@" >> {log}\nsleep {delay_s}\nexec g++ "$@"\n')
    cxx.chmod(cxx.stat().st_mode | stat.S_IXUSR)
    env = dict(os.environ, CXX=str(cxx), PYTHONPATH=str(REPO))
    env.pop("ATPU_DISABLE_NATIVE", None)
    return env, log


def _start_loader(native_dir: Path, env: dict) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(native_dir)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def _finish(proc: subprocess.Popen) -> tuple[list, str]:
    """What the child printed of ``available()`` and ``load_error()``, and
    its standard error."""
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    return json.loads(out.strip().splitlines()[-1]), err


def _result(proc: subprocess.Popen) -> list:
    return _finish(proc)[0]


def _links_of_the_library(log: Path) -> list[str]:
    return [line for line in log.read_text().splitlines() if "-shared" in line]


@needs_toolchain
def test_six_first_callers_run_one_build_and_all_load_it(tmp_path):
    native_dir = _copy_of_native(tmp_path)
    env, log = _logging_compiler(tmp_path)
    procs = [_start_loader(native_dir, env) for _ in range(6)]
    results = [_result(p) for p in procs]
    assert results == [[True, None]] * 6
    assert len(_links_of_the_library(log)) == 1, log.read_text()


@needs_toolchain
def test_a_loader_started_during_a_rebuild_gets_a_whole_library(tmp_path):
    native_dir = _copy_of_native(tmp_path)
    env, log = _logging_compiler(tmp_path)
    assert _result(_start_loader(native_dir, env)) == [True, None]
    # a source changes: the library is now older than it
    old = time.time() - 100
    for built in (native_dir / "build").iterdir():
        os.utime(built, (old, old))
    os.utime(native_dir / "store.cc", None)
    log.unlink()
    slow_env, _ = _logging_compiler(tmp_path, delay_s=1.0)
    builder = _start_loader(native_dir, slow_env)
    deadline = time.monotonic() + 60
    while not log.exists() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert log.exists(), "the builder never reached the compiler"
    loader = _start_loader(native_dir, slow_env)  # the rebuild is in flight
    assert _result(loader) == [True, None]
    assert _result(builder) == [True, None]
    # the loader waited for the builder; it did not build beside it
    assert len(_links_of_the_library(log)) == 1, log.read_text()


@needs_toolchain
def test_make_links_to_a_temporary_name_and_renames(tmp_path):
    native_dir = _copy_of_native(tmp_path)
    env, log = _logging_compiler(tmp_path)
    proc = subprocess.run(
        ["make", "-C", str(native_dir)], env=env, capture_output=True, text=True, timeout=300
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    outputs = [
        args[args.index("-o") + 1] for args in map(str.split, log.read_text().splitlines())
    ]
    assert len(outputs) == 2
    final = {"build/libagentainer_native.so", "build/loadgen"}
    assert not final & set(outputs), outputs
    assert {p.name for p in (native_dir / "build").iterdir()} == {
        "libagentainer_native.so",
        "loadgen",
    }


@needs_toolchain
def test_native_layer_builds_where_a_compiler_is():
    if native.available():
        assert native.load_error() is None
        return
    proc = subprocess.run(
        ["make", "-C", str(REPO / "native")], capture_output=True, text=True, timeout=300
    )
    tail = "\n".join((proc.stderr or proc.stdout).strip().splitlines()[-12:])
    pytest.fail(
        f"make and g++ are on PATH and the native library is unavailable: "
        f"{native.load_error()}\nmake -C native (rc {proc.returncode}):\n{tail}"
    )


@pytest.mark.skipif(not shutil.which("make"), reason="no make on PATH")
def test_a_failed_build_degrades_with_its_message(tmp_path):
    native_dir = tmp_path / "native"
    native_dir.mkdir()
    (native_dir / "Makefile").write_text("all:\n\t@echo no compiler here >&2; exit 2\n")
    env = dict(os.environ, PYTHONPATH=str(REPO))
    env.pop("ATPU_DISABLE_NATIVE", None)
    result, err = _finish(_start_loader(native_dir, env))
    assert result == [False, "native build failed (make -C native)"]
    assert "no compiler here" in err
