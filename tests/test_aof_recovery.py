"""AOF truncated-tail recovery through the Python-visible store path.

The C++ store stops replaying at a torn record (native/store.cc aof_load)
— these tests pin the full contract from NativeStore's surface:

* every COMPLETE record before the tear is recovered;
* the torn record is dropped (never half-applied);
* reopen-and-continue: the torn tail is truncated before the append
  handle opens, so post-recovery writes survive the NEXT reopen (they
  used to land after the unparseable bytes and silently vanish);
* parity: the recovered native state equals a MemoryStore replay of the
  same surviving operations — recovery is replay, not approximation.
"""

import os

import pytest

from agentainer_tpu.store import MemoryStore
from tests.conftest import _native_available


pytestmark = pytest.mark.skipif(
    not _native_available(), reason="native library unavailable"
)


def _new(path):
    from agentainer_tpu.store.native import NativeStore

    return NativeStore(aof_path=str(path))


# ops applied before the tear; the torn op is appended after these
_OPS = [
    ("set", "alpha", "1"),
    ("set", "beta", "two"),
    ("rpush", "queue", ["a", "b", "c"]),
    ("hset", "meta", ("field", "val")),
    ("sadd", "members", ["m1", "m2"]),
    ("set", "alpha", "rewritten"),  # later record wins on replay
]


def _apply(store):
    for op, key, arg in _OPS:
        if op == "set":
            store.set(key, arg)
        elif op == "rpush":
            store.rpush(key, *arg)
        elif op == "hset":
            store.hset(key, arg[0], arg[1])
        elif op == "sadd":
            store.sadd(key, *arg)


def _assert_parity(native):
    """Native recovered state must equal a MemoryStore replay of _OPS."""
    mem = MemoryStore()
    _apply(mem)
    assert native.get("alpha") == mem.get("alpha") == b"rewritten"
    assert native.get("beta") == mem.get("beta")
    assert native.lrange("queue", 0, -1) == mem.lrange("queue", 0, -1)
    assert native.hgetall("meta") == mem.hgetall("meta")
    assert native.smembers("members") == mem.smembers("members")


def test_torn_tail_recovers_complete_records(tmp_path):
    path = tmp_path / "store.aof"
    s = _new(path)
    _apply(s)
    s.rpush("torn", "x", "y")  # the record we will tear mid-bytes
    s.close()
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 3)

    s2 = _new(path)
    _assert_parity(s2)  # everything before the tear survived, exactly
    assert s2.lrange("torn", 0, -1) == []  # torn record dropped whole
    s2.close()


def test_reopen_and_continue_after_tear(tmp_path):
    """Writes made AFTER torn-tail recovery must survive the NEXT reopen:
    the recovered store truncates the tail before appending, so the log
    stays parseable end to end."""
    path = tmp_path / "store.aof"
    s = _new(path)
    _apply(s)
    s.rpush("torn", "x")
    s.close()
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        f.truncate(f.tell() - 2)

    s2 = _new(path)
    s2.set("post-recovery", "written-after-tear")
    s2.rpush("queue", "d")
    s2.close()

    s3 = _new(path)
    assert s3.get("post-recovery") == b"written-after-tear"
    assert s3.lrange("queue", 0, -1) == [b"a", b"b", b"c", b"d"]
    _ = s3.get("alpha") == b"rewritten"
    s3.close()


def test_tear_inside_length_prefix(tmp_path):
    """A tear inside the 4-byte length prefix itself (not the payload)
    still recovers cleanly — the loader must never read past the buffer."""
    path = tmp_path / "store.aof"
    s = _new(path)
    _apply(s)
    s.set("tail", "doomed")
    s.close()
    size = os.path.getsize(path)
    # the final record is 4(len) + payload; keep only 2 bytes of its prefix
    # (payload length for SET tail: op byte + argc + 2 length-prefixed args)
    with open(path, "rb") as f:
        data = f.read()
    # find the final record boundary by replaying lengths
    pos = 0
    last = 0
    while pos + 4 <= len(data):
        import struct

        (n,) = struct.unpack_from("<I", data, pos)
        if pos + 4 + n > len(data):
            break
        last = pos
        pos += 4 + n
    with open(path, "r+b") as f:
        f.truncate(last + 2)  # mid-length-prefix of the final record

    s2 = _new(path)
    assert s2.get("tail") is None  # the torn final record is gone
    _assert_parity(s2)
    s2.close()


def test_empty_and_garbage_aof(tmp_path):
    path = tmp_path / "store.aof"
    with open(path, "wb") as f:
        f.write(b"")  # empty file
    s = _new(path)
    assert s.get("anything") is None
    s.set("k", "v")
    s.close()
    s2 = _new(path)
    assert s2.get("k") == b"v"
    s2.close()
