"""Checkpoint plane: model weights + KV-cache snapshots.

Three tiers, mirroring and upgrading the reference's checkpoint story
(SURVEY.md §5.4: agent records in Redis, backup tarballs, in-agent
checkpoint patterns):

- **weights**: orbax PyTree checkpoints under a directory; ``load_params``
  restores into the model's pytree with the engine's dtype;
- **KV snapshots**: a single cache *slot* (one session's context) serialized
  to bytes for the store — this is what lets a restarted engine resume a
  conversation without re-prefilling (BASELINE.json config #3);
- agent records/backups live in the control plane (manager/backup.py).
"""

from __future__ import annotations

import io
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..models.configs import ModelConfig
from ..models.llama import KVCache


def save_params(params: dict, path: str | Path) -> None:
    import orbax.checkpoint as ocp

    path = Path(path).expanduser().resolve()
    ckptr = ocp.PyTreeCheckpointer()
    ckptr.save(path / "params", jax.device_get(params))


def load_params(cfg: ModelConfig, path: str | Path, dtype=jnp.bfloat16) -> dict:
    """Restore weights from either supported layout: an orbax PyTree dir
    (our own save_params) or a HuggingFace checkpoint dir (config.json +
    *.safetensors) via engine/hf_convert.py — the deploy-any-published-
    checkpoint path."""
    path = Path(path).expanduser().resolve()
    from .hf_convert import is_hf_checkpoint, load_hf_params

    if is_hf_checkpoint(path):
        return load_hf_params(cfg, path, dtype)
    import orbax.checkpoint as ocp

    ckptr = ocp.PyTreeCheckpointer()
    restored = ckptr.restore(path / "params")
    # host-side cast: the engine device_puts with its target sharding, so a
    # TP-sharded model never materializes whole on one chip
    return jax.tree.map(lambda x: np.asarray(x).astype(dtype), restored)


# -- cache slot snapshots (engine ↔ store) --------------------------------
# v2: KV ships in the cache's EXACT dtype (v1 cast everything to fp16,
# which rounded fp32/bf16 arenas on restore and broke the token-identical
# resume guarantee under near-tie greedy argmax). bfloat16 has no portable
# npz encoding (np.savez degrades it to a void dtype), so it travels as a
# uint16 bit-view with the true dtype recorded in the header.
# v3: paged-arena era. The payload layout is UNCHANGED (position-trimmed
# [L, pos, KV, hd] prefix in the exact dtype) — a paged engine stages it
# by gathering only the session's live pages, and the optional
# ``page_size`` header records that provenance — so v3 blobs restore into
# paged and dense engines alike.
# v4: named leaves. A slot is "positional leaves up to ``position`` +
# per-lane state leaves": the header's ``leaves`` maps each array's name to
# its true dtype and whether it is positional (axis 1 = positions, trimmed
# to ``position``) or per-lane (shipped whole: a recurrent state cannot be
# trimmed). A K/V family's leaves are ``k`` and ``v`` with v3's layout, so
# v1-v3 blobs written before the upgrade keep restoring (the reader accepts
# all four) and read as those two leaves.
SNAP_VERSION = 4


def _portable(a: np.ndarray) -> np.ndarray:
    return a.view(np.uint16) if a.dtype.name == "bfloat16" else a


def _true_dtype(a: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name == "bfloat16":
        import ml_dtypes

        return a.view(ml_dtypes.bfloat16)
    return a


def pack_snapshot(
    leaves: dict, position: int, meta: dict | None = None, positional: tuple = ("k", "v")
) -> bytes:
    """Host half of a slot snapshot: block on the staged device buffers
    (``name -> array``; positional leaves bucket-padded ``[L, bucket, ...]``
    — the engine's worker dispatched the slice), trim those to the live
    prefix, and pack a self-describing npz blob. Only the written prefix of
    a positional leaf ships — a 100-token conversation snapshot is ~100/S
    of the slot arena; a per-lane leaf ships whole."""
    arrays, described = {}, {}
    for name, staged in leaves.items():
        a = np.asarray(staged)
        if name in positional:
            a = a[:, :position]
        described[name] = {"dtype": a.dtype.name, "positional": name in positional}
        arrays[name] = _portable(a)
    header = {"version": SNAP_VERSION, "position": position, "leaves": described, **(meta or {})}
    if "k" in described:
        header["dtype"] = described["k"]["dtype"]  # what a v2/v3 reader looked for
    buf = io.BytesIO()
    np.savez_compressed(
        buf, header=np.frombuffer(json.dumps(header).encode(), dtype=np.uint8), **arrays
    )
    return buf.getvalue()


def pack_kv_snapshot(k16, v16, position: int, meta: dict | None = None) -> bytes:
    """A K/V family's snapshot: the two positional leaves ``k`` and ``v``."""
    return pack_snapshot({"k": k16, "v": v16}, position, meta)


def deserialize_snapshot(blob: bytes) -> tuple[dict, dict]:
    """Returns (``name -> array`` in each leaf's true dtype, header dict).
    Accepts v1-v3 blobs (``k`` and ``v`` ``[L, pos, KV, hd]``; v1: fp16
    payload) so snapshots taken before an engine upgrade still restore."""
    with np.load(io.BytesIO(blob)) as z:
        header = json.loads(bytes(z["header"]).decode())
        version = header.get("version")
        if version == 1:
            return {"k": z["k"], "v": z["v"]}, header  # legacy: fp16 as stored
        if version in (2, 3):
            name = header.get("dtype", "")
            return {"k": _true_dtype(z["k"], name), "v": _true_dtype(z["v"], name)}, header
        if version != SNAP_VERSION:
            raise ValueError(f"unsupported cache snapshot version: {version}")
        return {n: _true_dtype(z[n], d["dtype"]) for n, d in header["leaves"].items()}, header


def deserialize_kv_slot(blob: bytes) -> tuple[np.ndarray, np.ndarray, dict]:
    """Returns (k [L, pos, KV, hd], v, header dict) of a K/V family's
    snapshot, whatever version wrote it."""
    leaves, header = deserialize_snapshot(blob)
    if set(leaves) != {"k", "v"}:
        raise ValueError(f"not a K/V snapshot: leaves {sorted(leaves)}")
    return leaves["k"], leaves["v"], header


def restore_kv_slot(cache: KVCache, slot: int, k: np.ndarray, v: np.ndarray) -> KVCache:
    """Write a snapshot back into slot's prefix; rest of the arena unchanged."""
    position = k.shape[1]
    dtype = cache.k.dtype
    new_k = cache.k.at[:, slot, :position].set(jnp.asarray(k, dtype))
    new_v = cache.v.at[:, slot, :position].set(jnp.asarray(v, dtype))
    return KVCache(new_k, new_v)
