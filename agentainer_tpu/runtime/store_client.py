"""Async store client for engine subprocesses.

The reference's agents connect to Redis directly over the bridge network
(examples/gpt-agent/app.py:20-27). Engines here reach the daemon's store
two ways, fastest available first:

- **unix socket, binary protocol** (``AGENTAINER_STORE_SOCK``): frames of
  the native wire encoding (native/common.h) straight into the C++ store —
  no HTTP, no JSON, authenticated once per connection with the per-engine
  token;
- **HTTP** (``AGENTAINER_CONTROL_URL`` + ``/internal/store``): JSON ops,
  namespaced to the agent's ``agent:{id}:*`` keys.

Falls back to process-local memory when neither is configured (standalone
engine runs, unit tests).
"""

from __future__ import annotations

import asyncio
import os
import random
import struct
import time
from typing import Any

import aiohttp

from .. import faults
from ..core.resilience import backoff_delays

# single source of truth for the native wire codec: agentainer_tpu.store.native
# mirrors native/common.h; importing it has no side effects (CDLL load is lazy)
from ..store import native as _wire

_enc = _wire.encode_request
_dec = _wire.decode_response

# op-name → opcode, resolved from the one OP_* table ("delete" is OP_DEL)
_OP_NUM = {
    name: getattr(_wire, f"OP_{name.upper()}")
    for name in (
        "set", "get", "keys", "expire", "ttl",
        "rpush", "lpush", "lrem", "lrange", "llen", "ltrim",
        "hset", "hincrby", "hgetall", "pipeline", "auth",
    )
}
_OP_NUM["delete"] = _wire.OP_DEL

# Transport-shaped failures a retry can reasonably fix: the connection died,
# the peer vanished mid-frame, or the wait timed out. Everything else —
# protocol violations, auth rejections, programming errors — must surface
# unchanged; retrying those only hides the bug and delays the caller.
TRANSIENT_ERRORS = (
    OSError,  # ConnectionError and friends are subclasses
    asyncio.TimeoutError,
    asyncio.IncompleteReadError,  # EOFError subclass: peer closed mid-frame
    aiohttp.ClientConnectionError,
)


# a blob's part: under the store socket's 64 MiB frame (native/dataplane.cc
# ``handle_uds_conn``), with room for its base64 on the HTTP path
PART_BYTES = 32 << 20
_PARTS = b"ATPU-PARTS1 "


def _manifest(raw: bytes | None) -> tuple[str, int, int] | None:
    """``(generation, parts, size)`` of a blob stored in parts, else None."""
    if raw is None or not raw.startswith(_PARTS) or len(raw) > 128:
        return None
    try:
        generation, n, size = raw[len(_PARTS):].decode().split()
        return generation, int(n), int(size)
    except ValueError:
        return None


class _UDSPool:
    """Small pool of authenticated unix-socket connections to the native
    store; one frame in flight per connection."""

    def __init__(self, path: str, agent_id: str, token: str, size: int = 4):
        self.path = path
        self.agent_id = agent_id
        self.token = token
        self.size = size
        self._free: asyncio.Queue | None = None
        self._made = 0
        self._lock = asyncio.Lock()

    async def _connect(self):
        reader, writer = await asyncio.open_unix_connection(self.path)
        frame = _enc(_OP_NUM["auth"], [self.agent_id.encode(), self.token.encode()])
        writer.write(struct.pack("<I", len(frame)) + frame)
        await writer.drain()
        status, vals = await self._read_resp(reader)
        if status != 0:
            writer.close()
            raise RuntimeError(
                f"store auth failed: {vals[0].decode() if vals else 'unknown'}"
            )
        return reader, writer

    @staticmethod
    async def _read_resp(reader) -> tuple[int, list[bytes]]:
        raw_len = await reader.readexactly(4)
        (n,) = struct.unpack("<I", raw_len)
        return _dec(await reader.readexactly(n))

    async def roundtrip(self, frame: bytes) -> tuple[int, list[bytes]]:
        if self._free is None:
            async with self._lock:
                if self._free is None:
                    self._free = asyncio.Queue()
        conn = None
        if self._free.empty() and self._made < self.size:
            async with self._lock:
                if self._made < self.size:
                    self._made += 1
                    try:
                        conn = await self._connect()
                    except BaseException:
                        # ANY failure un-counts the slot (accounting, not
                        # classification — leaking it would shrink the pool
                        # forever); the exception itself propagates unchanged
                        self._made -= 1
                        raise
        if conn is None:
            conn = await self._free.get()
        reader, writer = conn
        try:
            writer.write(struct.pack("<I", len(frame)) + frame)
            await writer.drain()
            resp = await self._read_resp(reader)
        except TRANSIENT_ERRORS:
            # transport failure: this connection is dead or desynced — drop
            # it (the next call dials fresh) and let the caller's bounded
            # retry decide whether to go again
            self._made -= 1
            writer.close()
            raise
        except BaseException as e:
            # unexpected (codec bug, cancellation): the connection may be
            # mid-frame and can't be reused either, but the error must
            # surface loudly as what it is — not silently degrade into
            # "store op failed" like the old blanket handler
            self._made -= 1
            writer.close()
            if not isinstance(e, asyncio.CancelledError):
                print(
                    f"[store-client] non-transport error on store socket: "
                    f"{type(e).__name__}: {e}",
                    flush=True,
                )
            raise
        self._free.put_nowait(conn)
        return resp

    def close(self) -> None:
        if self._free is None:
            return
        while not self._free.empty():
            _, writer = self._free.get_nowait()
            writer.close()


class StoreClient:
    def __init__(
        self,
        control_url: str = "",
        token: str = "",
        agent_id: str = "",
        store_sock: str = "",
        retries: int | None = None,
        retry_base_s: float | None = None,
    ):
        self.control_url = control_url.rstrip("/")
        self.token = token
        self.agent_id = agent_id
        self._session: aiohttp.ClientSession | None = None
        self._local: dict[str, Any] = {}  # fallback when no control plane
        self._uds = (
            _UDSPool(store_sock, agent_id, token)
            if store_sock and agent_id and token
            else None
        )
        # Bounded retry + jittered exponential backoff for TRANSIENT
        # transport errors only (a refused/reset connection, a timeout, a
        # torn frame) — a store blip must degrade one op's latency, not
        # fail the request it serves. Non-idempotency caveat: an ack lost
        # in flight can double-apply an rpush on retry; that costs at worst
        # a duplicated conversation turn, which the durability guarantee
        # tolerates (same envelope as Redis client retries).
        if retries is None:
            try:
                retries = int(os.environ.get("ATPU_STORE_RETRIES", "3"))
            except ValueError:
                retries = 3
        if retry_base_s is None:
            try:
                retry_base_s = float(os.environ.get("ATPU_STORE_RETRY_BASE_S", "0.05"))
            except ValueError:
                retry_base_s = 0.05
        self.retries = max(0, retries)
        self.retry_base_s = retry_base_s
        self._retry_rng = random.Random(0xA70)  # deterministic jitter
        self.retries_total = 0
        self.transient_errors_total = 0

    @classmethod
    def from_env(cls) -> "StoreClient":
        return cls(
            control_url=os.environ.get("AGENTAINER_CONTROL_URL", ""),
            token=os.environ.get("AGENTAINER_INTERNAL_TOKEN", ""),
            agent_id=os.environ.get("AGENTAINER_AGENT_ID", ""),
            store_sock=os.environ.get("AGENTAINER_STORE_SOCK", ""),
        )

    @property
    def connected(self) -> bool:
        return bool(self.control_url) or self._uds is not None

    async def close(self) -> None:
        if self._session is not None:
            await self._session.close()
            self._session = None
        if self._uds is not None:
            self._uds.close()

    async def _post(self, payload: dict[str, Any], label: str) -> Any:
        if self._session is None:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10),
                headers={
                    "Authorization": f"Bearer {self.token}",
                    "X-Agentainer-Agent-ID": self.agent_id,
                },
            )
        async with self._session.post(
            f"{self.control_url}/internal/store", json=payload
        ) as resp:
            doc = await resp.json()
            if resp.status != 200:
                raise RuntimeError(f"store {label} failed: {doc.get('message')}")
            return doc.get("data")

    # -- binary encoding of the HTTP op shapes ---------------------------
    @staticmethod
    def _encode_sub(op: str, key: str, kw: dict) -> bytes:
        import base64 as _b64

        k = key.encode()
        if op == "get" or op == "get_b64":
            return _enc(_OP_NUM["get"], [k])
        if op == "set":
            ttl = kw.get("ttl")
            return _enc(
                _OP_NUM["set"],
                [k, str(kw.get("value", "")).encode(), b"" if ttl is None else repr(float(ttl)).encode()],
            )
        if op == "set_b64":
            ttl = kw.get("ttl")
            return _enc(
                _OP_NUM["set"],
                [k, _b64.b64decode(kw.get("value_b64", "")), b"" if ttl is None else repr(float(ttl)).encode()],
            )
        if op == "delete":
            return _enc(_OP_NUM["delete"], [k])
        if op == "expire":
            return _enc(_OP_NUM["expire"], [k, repr(float(kw.get("ttl", 0))).encode()])
        if op == "rpush":
            return _enc(_OP_NUM["rpush"], [k] + [str(v).encode() for v in kw.get("values", [])])
        if op == "lrange":
            return _enc(
                _OP_NUM["lrange"],
                [k, str(kw.get("start", 0)).encode(), str(kw.get("stop", -1)).encode()],
            )
        if op == "ltrim":
            return _enc(
                _OP_NUM["ltrim"],
                [k, str(kw.get("start", 0)).encode(), str(kw.get("stop", -1)).encode()],
            )
        if op == "llen":
            return _enc(_OP_NUM["llen"], [k])
        if op == "hincrby":
            return _enc(
                _OP_NUM["hincrby"],
                [k, str(kw.get("field", "")).encode(), str(kw.get("amount", 1)).encode()],
            )
        if op == "hgetall":
            return _enc(_OP_NUM["hgetall"], [k])
        if op == "keys":
            return _enc(_OP_NUM["keys"], [str(kw.get("pattern", key + "*")).encode()])
        raise ValueError(f"op {op!r} not supported over the store socket")

    @staticmethod
    def _decode_result(op: str, status: int, vals: list[bytes]) -> Any:
        import base64 as _b64

        if status == 1:
            raise RuntimeError(vals[0].decode("utf-8", "replace") if vals else "store error")
        if status == 2:  # nil
            return None
        if op == "get":
            return vals[0].decode("utf-8", "replace") if vals else None
        if op == "get_b64":
            return _b64.b64encode(vals[0]).decode() if vals else None
        if op in ("delete", "rpush", "llen", "hincrby", "lrem", "expire"):
            return int(vals[0]) if vals else 0
        if op in ("lrange", "keys"):
            return [v.decode("utf-8", "replace") for v in vals]
        if op == "hgetall":
            return {
                vals[i].decode("utf-8", "replace"): vals[i + 1].decode("utf-8", "replace")
                for i in range(0, len(vals), 2)
            }
        return None  # set/ltrim/set_b64

    async def _with_retry(self, attempt):
        """Run one transport attempt, retrying TRANSIENT_ERRORS on the
        jittered backoff schedule; anything else surfaces immediately.
        The schedule is built lazily on the FIRST failure: the happy path
        pays nothing, and the deterministic jitter sequence is a function
        of failures, not of total op count."""
        delays: list[float] | None = None
        n = 0
        while True:
            try:
                return await attempt()
            except TRANSIENT_ERRORS:
                self.transient_errors_total += 1
                if delays is None:
                    delays = backoff_delays(
                        self.retries, base_s=self.retry_base_s, rng=self._retry_rng
                    )
                if n >= len(delays):
                    raise
                self.retries_total += 1
                await asyncio.sleep(delays[n])
                n += 1

    async def _op(self, op: str, key: str, **kw: Any) -> Any:
        if not self.connected:
            return self._local_op(op, key, **kw)

        async def attempt():
            # failpoint cut INSIDE the retry loop: an injected transient
            # error exercises the recovery path, not just the failure path
            await faults.fire_async("store_client.rpc")
            if self._uds is not None:
                status, vals = await self._uds.roundtrip(self._encode_sub(op, key, kw))
                return self._decode_result(op, status, vals)
            return await self._post({"op": op, "key": key, **kw}, f"op {op}")

        return await self._with_retry(attempt)

    async def pipeline(self, ops: list[dict[str, Any]]) -> list[Any]:
        """Run a batch of ops in one round-trip (each: {op, key, ...})."""
        if not self.connected:
            return [
                self._local_op(
                    o["op"], o["key"], **{k: v for k, v in o.items() if k not in ("op", "key")}
                )
                for o in ops
            ]

        async def attempt():
            await faults.fire_async("store_client.rpc")
            if self._uds is not None:
                subs = [
                    self._encode_sub(
                        o["op"], o["key"], {k: v for k, v in o.items() if k not in ("op", "key")}
                    )
                    for o in ops
                ]
                status, vals = await self._uds.roundtrip(_enc(_OP_NUM["pipeline"], subs))
                if status != 0:
                    raise RuntimeError(
                        vals[0].decode("utf-8", "replace") if vals else "pipeline failed"
                    )
                return [
                    self._decode_result(o["op"], *_dec(raw)) for o, raw in zip(ops, vals)
                ]
            return await self._post({"op": "pipeline", "ops": ops}, "pipeline") or []

        return await self._with_retry(attempt)

    def _local_op(self, op: str, key: str, **kw: Any) -> Any:
        d = self._local
        if op == "get":
            return d.get(key)
        if op == "set":
            d[key] = kw.get("value", "")
            return None
        if op == "set_b64":
            d[key] = kw.get("value_b64", "")
            return None
        if op == "get_b64":
            return d.get(key)
        if op == "delete":
            return 1 if d.pop(key, None) is not None else 0
        if op == "expire":
            # the in-process fallback dict has no expiry sweeper; standalone
            # state dies with the process, so acknowledging is correct
            return 1 if key in d else 0
        if op == "rpush":
            d.setdefault(key, []).extend(kw.get("values", []))
            return len(d[key])
        if op == "lrange":
            lst = d.get(key, [])
            stop = kw.get("stop", -1)
            return lst[kw.get("start", 0) : (stop + 1 if stop != -1 else None)]
        if op == "ltrim":
            lst = d.get(key, [])
            stop = kw.get("stop", -1)
            d[key] = lst[kw.get("start", 0) : (stop + 1 if stop != -1 else None)]
            return None
        if op == "llen":
            return len(d.get(key, []))
        if op == "hincrby":
            h = d.setdefault(key, {})
            h[kw.get("field", "")] = int(h.get(kw.get("field", ""), 0)) + kw.get("amount", 1)
            return h[kw.get("field", "")]
        if op == "hgetall":
            return {k: str(v) for k, v in d.get(key, {}).items()}
        if op == "keys":
            import fnmatch

            return [k for k in d if fnmatch.fnmatchcase(k, kw.get("pattern", key + "*"))]
        raise ValueError(f"unknown op {op}")

    # -- typed helpers ---------------------------------------------------
    async def get(self, key: str) -> str | None:
        return await self._op("get", key)

    async def set(self, key: str, value: str, ttl: float | None = None) -> None:
        await self._op("set", key, value=value, ttl=ttl)

    async def set_bytes(self, key: str, blob: bytes, ttl: float | None = None) -> None:
        """``blob`` under ``key``. One over :data:`PART_BYTES` (the store
        socket closes a connection on a frame over 64 MiB: a long session's
        snapshot is hundreds of MB) goes in parts, ``key:part:<generation>:<i>``,
        and ``key`` holds a manifest naming them, written LAST: a reader, or a
        crash in the middle, finds the generation before whole. The
        generation before is deleted once the new one stands."""
        if len(blob) <= PART_BYTES:
            await self._set_blob(key, blob, ttl)
            return
        old = _manifest(await self._get_blob(key))
        generation = f"{time.time_ns():x}"
        n = -(-len(blob) // PART_BYTES)
        view = memoryview(blob)
        for i in range(n):
            await self._set_blob(f"{key}:part:{generation}:{i}", bytes(view[i * PART_BYTES : (i + 1) * PART_BYTES]), ttl)
        await self._set_blob(key, _PARTS + f"{generation} {n} {len(blob)}".encode(), ttl)
        if old is not None:
            for i in range(old[1]):
                await self.delete(f"{key}:part:{old[0]}:{i}")

    async def get_bytes(self, key: str) -> bytes | None:
        raw = await self._get_blob(key)
        parts = _manifest(raw)
        if parts is None:
            return raw
        generation, n, size = parts
        got = [await self._get_blob(f"{key}:part:{generation}:{i}") for i in range(n)]
        if any(p is None for p in got) or sum(map(len, got)) != size:
            return None  # a part expired or was lost: no snapshot, never a torn one
        return b"".join(got)

    async def _set_blob(self, key: str, blob: bytes, ttl: float | None) -> None:
        """One value of bytes. The store socket's frames carry bytes as they
        are; only the HTTP path's JSON needs base64 (encoded off the loop's
        thread: 32 MiB hold the interpreter for tens of ms)."""
        if self.connected and self._uds is not None:
            frame = _enc(_OP_NUM["set"], [key.encode(), blob, b"" if ttl is None else repr(float(ttl)).encode()])

            async def attempt():
                await faults.fire_async("store_client.rpc")
                return self._decode_result("set", *await self._uds.roundtrip(frame))

            await self._with_retry(attempt)
            return
        import base64

        value = await asyncio.to_thread(lambda: base64.b64encode(blob).decode())
        await self._op("set_b64", key, value_b64=value, ttl=ttl)

    async def _get_blob(self, key: str) -> bytes | None:
        if self.connected and self._uds is not None:
            async def attempt():
                await faults.fire_async("store_client.rpc")
                status, vals = await self._uds.roundtrip(_enc(_OP_NUM["get"], [key.encode()]))
                if status == 1:
                    raise RuntimeError(vals[0].decode("utf-8", "replace") if vals else "store error")
                return vals[0] if status == 0 and vals else None

            return await self._with_retry(attempt)
        import base64

        raw = await self._op("get_b64", key)
        return None if raw is None else base64.b64decode(raw)

    async def delete(self, key: str) -> int:
        return await self._op("delete", key)

    async def expire(self, key: str, ttl: float) -> bool:
        return bool(await self._op("expire", key, ttl=ttl))

    async def rpush(self, key: str, *values: str) -> int:
        return await self._op("rpush", key, values=list(values))

    async def lrange(self, key: str, start: int = 0, stop: int = -1) -> list[str]:
        return await self._op("lrange", key, start=start, stop=stop) or []

    async def ltrim(self, key: str, start: int, stop: int) -> None:
        await self._op("ltrim", key, start=start, stop=stop)

    async def llen(self, key: str) -> int:
        return await self._op("llen", key) or 0

    async def hincrby(self, key: str, field: str, amount: int = 1) -> int:
        return await self._op("hincrby", key, field=field, amount=amount)

    async def hgetall(self, key: str) -> dict[str, str]:
        return await self._op("hgetall", key) or {}

    async def keys(self, pattern: str) -> list[str]:
        return await self._op("keys", pattern.split("*")[0], pattern=pattern) or []
