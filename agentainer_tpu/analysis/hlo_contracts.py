"""Declarative contracts over compiled HLO.

The sharding invariants that keep serving fast are *compiler outputs*,
not source properties: GSPMD may legally insert an all-gather of the KV
arena, XLA may legally copy a "donated" buffer, a shape-key change may
legally trigger a recompile storm. Each contract here turns one of those
silent regressions into a loud assertion, and the ``tests/test_*_hlo.py``
files consume these instead of re-implementing the HLO scanning (three
copies of the same never-all-gather scan predate this module).

Usage::

    hlo = compile_hlo(fn, *args)
    check(hlo, NoLargeAllGather(shard_elems), HasCrossReduction())
    check(hlo, DonationAliased(param_indices={1}))

    with recompile_budget(engine_jit_fns(engine), budget=0):
        ...scripted mixed workload...
"""

from __future__ import annotations

import math
import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Iterable

__all__ = [
    "ContractViolation",
    "NoLargeAllGather",
    "HasCrossReduction",
    "DonationAliased",
    "ArenaRidesInCarry",
    "StacksRideInCarry",
    "MixedStepOverStacks",
    "check",
    "compile_hlo",
    "op_result_elems",
    "jit_cache_size",
    "engine_jit_fns",
    "compile_count",
    "recompile_budget",
]


class ContractViolation(AssertionError):
    """An HLO contract failed; the message carries the offending lines."""


_RESULT_SHAPE = re.compile(r"=\s+\w+\[([0-9,]*)\]")


def op_result_elems(line: str) -> int:
    """Element count of the first shaped result on an HLO text line.
    (Factored out of test_sp_decode_hlo/test_spec_verify_hlo/test_paged_hlo
    — the single definition all three now share.)"""
    m = _RESULT_SHAPE.search(line)
    if not m or not m.group(1):
        return 0
    n = 1
    for d in m.group(1).split(","):
        n *= int(d)
    return n


def compile_hlo(fn: Callable, *args, **kwargs) -> str:
    """Lower + compile ``fn`` for ``args`` and return the final HLO text
    (post-SPMD-partitioning: collectives are visible as instructions)."""
    import jax

    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


@dataclass
class NoLargeAllGather:
    """No all-gather at or above ``min_elems`` result elements.

    The never-all-gather invariant: under a tp mesh the KV arena (or
    page pool) must stay shard-local — an all-gather the size of one
    chip's shard means GSPMD re-materialized the whole cache and the
    sharding is decorative. Small all-gathers (control scalars, the
    vocab-sharded logit max) are legitimate traffic and pass.
    """

    min_elems: int
    what: str = "the KV shard"

    def failures(self, hlo: str) -> list[str]:
        gathers = [ln for ln in hlo.splitlines() if "all-gather" in ln and "=" in ln]
        big = [ln.strip() for ln in gathers if op_result_elems(ln) >= self.min_elems]
        if big:
            return [f"all-gather of {self.what} (>= {self.min_elems} elems):"] + big
        return []


@dataclass
class HasCrossReduction:
    """At least one cross-shard reduction (all-reduce / reduce-scatter)
    exists — the sharded computation actually communicates. Zero
    reductions means the sharding constraint was dropped and each chip
    computed the full answer."""

    def failures(self, hlo: str) -> list[str]:
        reduces = [
            ln
            for ln in hlo.splitlines()
            if ("all-reduce" in ln or "reduce-scatter" in ln) and "=" in ln
        ]
        if not reduces:
            return ["no cross-shard reduction found — sharding was dropped?"]
        return []


_ALIAS_PARAM = re.compile(r"\(\s*(\d+)\s*,")


def donated_params(hlo: str) -> set[int]:
    """Parameter indices that actually alias an output in compiled HLO.

    Parses the module header's ``input_output_alias={ {out}: (param,
    {sub}, kind), ... }`` table; the braces nest, so the block is found
    by brace counting rather than regex.
    """
    start = hlo.find("input_output_alias={")
    if start < 0:
        return set()
    i = start + len("input_output_alias=")
    depth = 0
    end = i
    for end in range(i, len(hlo)):
        if hlo[end] == "{":
            depth += 1
        elif hlo[end] == "}":
            depth -= 1
            if depth == 0:
                break
    block = hlo[i : end + 1]
    return {int(n) for n in _ALIAS_PARAM.findall(block)}


@dataclass
class DonationAliased:
    """Donated buffers must actually alias in the compiled module.

    ``donate_argnums`` is a *permission*, not a guarantee: when dtypes or
    layouts mismatch, XLA silently copies instead of aliasing and the
    engine pays double HBM for every KV arena — exactly the failure mode
    that would erase the paged pool's capacity math. This contract reads
    the module's ``input_output_alias`` table and demands each listed
    parameter index appear.
    """

    param_indices: set[int] = field(default_factory=set)
    # pytree flattening makes exact parameter indices brittle — min_count
    # asserts "at least N parameters alias" (e.g. both KV cache leaves)
    min_count: int = 0

    def failures(self, hlo: str) -> list[str]:
        aliased = donated_params(hlo)
        out: list[str] = []
        missing = sorted(set(self.param_indices) - aliased)
        if missing:
            out.append(
                f"donated parameters {missing} do not alias any output "
                f"(aliased set: {sorted(aliased)}) — XLA inserted a copy"
            )
        if len(aliased) < self.min_count:
            out.append(
                f"only {len(aliased)} parameters alias an output "
                f"(need >= {self.min_count}) — a donated buffer is being copied"
            )
        return out


_WHILE = re.compile(r"stablehlo\.while\(.*?\) : (.*)$", re.M)
# a scatter carries a region, so its type signature closes the region lines
# later; a dynamic_update_slice is one line. Either way: (operand, ..update..)
_SCATTER = re.compile(
    r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>), tensor<[^>]*>, (tensor<[^>]*>)\) -> ',
    re.S,
)
_DUS = re.compile(
    r"stablehlo\.dynamic_update_slice .*? : \((tensor<[^>]*>), (tensor<[^>]*>),"
)


def _tensor_dims(t: str) -> tuple[int, ...]:
    """``tensor<2x4x256x2x16xf32>`` → ``(2, 4, 256, 2, 16)``."""
    return tuple(int(d) for d in t[len("tensor<") : -1].split("x")[:-1])


def _while_carries(text: str) -> list[list[tuple[int, ...]]]:
    """The shapes each while loop of a lowered program carries."""
    return [
        [_tensor_dims(t) for t in re.findall(r"tensor<[^>]*>", m.group(1))]
        for m in _WHILE.finditer(text)
    ]


def _writes(text: str) -> list[tuple[tuple[int, ...], tuple[int, ...]]]:
    """``(operand shape, update shape)`` of every scatter and
    dynamic_update_slice of a lowered program."""
    return [
        (_tensor_dims(m.group(1)), _tensor_dims(m.group(2)))
        for rx in (_SCATTER, _DUS)
        for m in rx.finditer(text)
    ]


@dataclass
class ArenaRidesInCarry:
    """The stacked KV arena stays one buffer through a step program: over
    the LOWERED (StableHLO) text of ``jit_decode_n`` / ``jit_verify`` /
    ``jit_prefill`` / ``jit_prefill_with_decode``.

    - Each K and V stack is a carried value of exactly ``loops`` while loops
      (the layer scan; for ``jit_decode_n`` the step scan around it too; one
      more loop over the layers would stream the weights once more), and a
      loop that carries the arena carries exactly the two stacks: the
      ``xs``/``ys`` form of a scan carries four (the stacks it slices a
      layer out of and the stacks it rebuilds layer by layer).
    - Every write into a stack (scatter or dynamic_update_slice whose
      operand has the arena's shape) updates exactly the step's new rows
      ``[B, T, KV, hd]`` (``rows``: one shape, or one per group of rows the
      step writes) — never a layer ``[B, S, KV, hd]``, which is what
      a stacked scan output writes back in every layer-step.

    Reads are not judged here: an implementation that cannot address the
    stack by layer (the XLA reference on CPU) takes ``stack[layer]`` itself
    and says so (``CacheAttention.arena``). That the donated arena aliases
    the output through the loops is ``DonationAliased`` on the compiled
    module.
    """

    arena: tuple[int, ...]  # [L, B, S, KV, hd]
    rows: tuple  # [B, T, KV, hd], or a tuple of such shapes
    loops: int = 1

    def failures(self, text: str) -> list[str]:
        out: list[str] = []
        arena = tuple(self.arena)
        groups = self.rows if isinstance(self.rows[0], (tuple, list)) else (self.rows,)
        n_rows = {math.prod(g) for g in groups}
        # arena-shaped values each while loop carries, for the loops that carry any
        carrying = [
            n
            for m in _WHILE.finditer(text)
            if (n := sum(_tensor_dims(t) == arena for t in re.findall(r"tensor<[^>]*>", m.group(1))))
        ]
        if len(carrying) < self.loops:
            out.append(
                f"the arena {list(self.arena)} is carried by {len(carrying)} "
                f"while loops (need {self.loops}): it is sliced and restacked "
                "around the layer loop, not carried through it"
            )
        if len(carrying) > self.loops:
            out.append(
                f"the arena is carried by {len(carrying)} while loops (want exactly "
                f"{self.loops}): a second loop over the layers reads the weights again"
            )
        if any(n != 2 for n in carrying):
            out.append(
                f"loops carry {carrying} arena-shaped values each (want 2: the "
                "K and V stacks) — a stacked scan output beside its input?"
            )
        updates = [upd for operand, upd in _writes(text) if operand == arena]
        if len(updates) < 2 * len(groups):
            out.append(
                f"found {len(updates)} writes into the arena (need K and V of "
                f"{len(groups)} group(s) of rows)"
            )
        bad = [u for u in updates if math.prod(u) not in n_rows]
        if bad:
            out.append(
                f"writes into the arena of shapes {bad}: every write must be the "
                f"step's new rows {[list(g) for g in groups]} ({sorted(n_rows)} elements), "
                f"a layer is {math.prod(arena[1:])}"
            )
        return out


@dataclass
class StacksRideInCarry:
    """``ArenaRidesInCarry`` for a cache of several kinds of leaf (the hybrid
    block's latent rows, recurrent state and conv state): over the LOWERED
    (StableHLO) text of ``jit_decode_n`` / ``jit_prefill``.

    - Each stack is a carried value of at least ``loops`` while loops (the
      layer scan, the 0-or-1-trip loop of the mixer that updates it, and for
      ``jit_decode_n`` the step scan around them), and a loop that carries a
      stack carries it once: not the ``xs``/``ys`` pair of a scan that slices
      a layer out and restacks it.
    - Every write into a stack (scatter or dynamic_update_slice on an operand
      of the stack's shape) updates at most ``updates[name]`` elements: the
      step's new rows of a positional leaf, the stepping lanes' state of a
      per-lane leaf — never more than it was told a step may touch.

    That the donated leaves alias the outputs through the loops is
    ``DonationAliased`` on the compiled module; that the chip's compiler adds
    no copy of its own is the described-v5e compile in tests/test_tpu_compile.py.
    """

    stacks: dict  # name -> shape
    updates: dict  # name -> most elements one write may update
    loops: int = 2

    def failures(self, text: str) -> list[str]:
        out: list[str] = []
        whiles, writes = _while_carries(text), _writes(text)
        for name, shape in self.stacks.items():
            shape = tuple(shape)
            carrying = [n for dims in whiles if (n := sum(d == shape for d in dims))]
            if len(carrying) < self.loops:
                out.append(
                    f"{name} {list(shape)} is carried by {len(carrying)} while loops "
                    f"(need {self.loops}): sliced and restacked around a loop, not carried through it"
                )
            if any(n != 1 for n in carrying):
                out.append(f"loops carry {carrying} values of {name}'s shape each (want 1)")
            sizes = [math.prod(upd) for operand, upd in writes if operand == shape]
            if not sizes:
                out.append(f"found no write into {name}")
            if any(n > self.updates[name] for n in sizes):
                out.append(
                    f"writes into {name} of {sizes} elements: a step may update "
                    f"{self.updates[name]}, a layer is {math.prod(shape[1:])}"
                )
        return out


@dataclass
class MixedStepOverStacks:
    """``ArenaRidesInCarry``'s two groups of rows for the hybrid block's cache
    (latent rows, or K and V; beside a linear mixer's state and conv where the
    block has one): over the LOWERED (StableHLO) text of
    ``jit_prefill_with_decode``.

    - Each stack is a carried value, once, of exactly ``loops`` while loops:
      the layer scan, and where the block has both kinds of mixer the
      0-or-1-trip loop round the stack's own (``loops = 2``). One loop more is
      a second pass over the layers (a chunk's forward and then a decode
      step's), which reads every weight again: what the program exists to
      avoid.
    - A positional stack takes exactly two writes: the chunk's ``chunk`` rows
      and the lanes' ``lanes`` rows, each row once, never a layer.
    - A per-lane stack (``per_lane``: state, conv) takes exactly two too: the
      chunk's ONE lane and the step's ``lanes`` lanes, a group at a time; or,
      named in ``joined`` (conv: the chunk's window joins the B before they
      step), the ``lanes`` lanes in ONE write.
    - No value is ``[chunk + lanes, vocab]`` or ``[chunk, vocab]``: the head
      runs on the ``1 + lanes`` rows somebody reads (ask with a chunk that is
      not the model's width: the head's own matrix is ``[dim, vocab]``).

    That the donated cache and carry alias the outputs is ``DonationAliased``
    on the compiled module; that the chip's compiler keeps one call of each
    kernel and one grouped FFN in the loop's body, and copies no stack, is
    the described-v5e compile in tests/test_tpu_compile.py.
    """

    stacks: dict  # name -> shape [n, B, S, ...]
    chunk: int
    lanes: int
    vocab: int
    per_lane: dict = field(default_factory=dict)  # name -> shape [n, B, ...]
    loops: int = 1
    joined: tuple = ()  # the per-lane stacks written once, all ``lanes`` lanes

    def failures(self, text: str) -> list[str]:
        out: list[str] = []
        whiles, writes = _while_carries(text), _writes(text)
        named = {**self.stacks, **self.per_lane}
        for name, shape in named.items():
            shape = tuple(shape)
            twins = sum(tuple(s) == shape for s in named.values())  # K and V: two stacks of one shape
            carrying = [n for dims in whiles if (n := sum(d == shape for d in dims))]
            if not carrying:
                out.append(f"{name} {list(shape)} is carried by no while loop: sliced and restacked around the layer loop")
            if len(carrying) > self.loops:
                out.append(
                    f"{name} is carried by {len(carrying)} while loops (want exactly {self.loops}): "
                    "a second loop over the layers reads the weights again"
                )
            if any(n != twins for n in carrying):
                out.append(f"loops carry {carrying} values of {name}'s shape each (want {twins})")
            sizes = sorted(math.prod(upd) for operand, upd in writes if operand == shape)
            if name in self.per_lane:
                lane = math.prod(shape[2:])
                want, what = sorted((lane, self.lanes * lane) * twins), "the chunk's lane and the step's lanes"
                if name in self.joined:
                    want, what = [self.lanes * lane] * twins, "the step's lanes, the chunk's among them"
            else:
                row = math.prod(shape[3:])
                want, what = sorted((self.chunk * row, self.lanes * row) * twins), "the chunk's rows and the lanes'"
            if sizes != want:
                out.append(f"writes into {name} of {sizes} elements: want {what} {want}, a layer is {math.prod(shape[1:])}")
        tall = {
            t for t in re.findall(r"tensor<[^>]*>", text)
            if "x" in t and (dims := _tensor_dims(t))[-1:] == (self.vocab,)
            and math.prod(dims) in (self.chunk * self.vocab, (self.chunk + self.lanes) * self.vocab)
        }
        if tall:
            out.append(f"values of shape {sorted(tall)}: the head runs on rows nobody reads (want 1 + {self.lanes})")
        return out


@dataclass
class ExpertsSeeOnlyTheirRows:
    """A step program over the cut (``ops/moe.sorted_from_rows``) computes
    only the (token, chosen expert) pairs: over the LOWERED (StableHLO) text
    of ``jit_prefill``. No value holds an F-wide activation of every row for
    every expert (``[rows, E, F]``, what the all-experts einsum's gate and up
    projections produce). That a grouped matmul stands in its place is the
    traced program's to show (``ragged_dot`` off the TPU, which StableHLO
    spells out, the ``moe_grouped_ffn`` kernel on it), and that nothing of
    expert size is copied on the way the compiled one's
    (tests/test_tpu_compile.py)."""

    rows: int
    experts: int
    ffn: int

    def failures(self, text: str) -> list[str]:
        out: list[str] = []
        want = (self.rows, self.experts, self.ffn)
        wide = {
            t for t in re.findall(r"tensor<[^>]*>", text)
            if "x" in t and (dims := _tensor_dims(t))[-3:] == want and math.prod(dims) == math.prod(want)
        }
        if wide:
            out.append(
                f"values of shape {sorted(wide)}: every row goes through every "
                f"expert ({self.experts} × the rows of FLOPs the router asked for)"
            )
        return out


def check(hlo: str, *contracts) -> None:
    """Assert every contract against one compiled-HLO text."""
    problems: list[str] = []
    for c in contracts:
        problems.extend(c.failures(hlo))
    if problems:
        raise ContractViolation("\n".join(problems))


# ---------------------------------------------------------------------------
# recompile budget


def jit_cache_size(fn) -> int:
    """Number of compiled variants a jitted callable holds (0 for plain
    callables — dict-of-jit caches count their entries instead)."""
    size = getattr(fn, "_cache_size", None)
    if callable(size):
        try:
            return int(size())
        except Exception as e:  # jax internals moved: surface, don't guess
            raise ContractViolation(f"jit cache size unreadable: {e}") from e
    return 0


def engine_jit_fns(engine) -> dict[str, object]:
    """The LLMEngine's compiled entry points, by name: the direct jit
    handles plus every keyed compile cache (snap buckets, verify buckets,
    prefix fork/slice buckets, paged snapshot/restore). The names are the
    compile-key families the recompile budget is written against."""
    fns: dict[str, object] = {}
    for attr in (
        "_prefill", "_prefill_with_decode", "_first_token", "_decode_n", "_inject",
        "_alloc_cache", "_alloc_carry",
    ):
        fn = getattr(engine, attr, None)
        if fn is not None:
            fns[attr] = fn
    for attr in (
        "_snap_fns",
        "_verify_fns",
        "_snap_paged_fns",
        "_restore_paged_fns",
        "_prefix_slice_fns",
        "_prefix_fork_fns",
        "_fused_fns",
    ):
        cache = getattr(engine, attr, None)
        if isinstance(cache, dict):
            for key, fn in cache.items():
                fns[f"{attr}[{key}]"] = fn
    fn = getattr(engine, "_page_copy_fn_cached", None)
    if fn is not None:
        fns["_page_copy_fn_cached"] = fn
    return fns


def compile_count(fns: dict[str, object]) -> dict[str, int]:
    """Per-family compiled-variant counts (dict caches count as 1 per
    entry: each keyed fn is its own compile)."""
    return {name: max(1, jit_cache_size(fn)) for name, fn in fns.items()}


@contextmanager
def recompile_budget(fns_before: Callable[[], dict[str, object]], budget: int):
    """Fail if the scripted workload inside the block compiles more than
    ``budget`` NEW variants across the engine's compile-key families.

    Warmup is the engine's promise: decode-chunk ladder x verify buckets x
    paged dispatch are all pre-compiled, so a steady mixed workload must
    compile ~0 new programs. A shape-key regression (a stray non-bucketed
    dimension reaching a jit signature) shows up here as a positive delta.
    """
    before = compile_count(fns_before())
    yield
    after = compile_count(fns_before())
    grew = {
        name: (before.get(name, 0), n)
        for name, n in after.items()
        if n > before.get(name, 0)
    }
    new_total = sum(n - b for b, n in grew.values())
    if new_total > budget:
        detail = ", ".join(f"{k}: {b}->{n}" for k, (b, n) in sorted(grew.items()))
        raise ContractViolation(
            f"recompile budget exceeded: {new_total} new compiled variants "
            f"(budget {budget}) — {detail}"
        )
