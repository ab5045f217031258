"""Slice scheduler — chip/HBM placement for agents.

No reference counterpart: the reference's "placement" is Docker putting every
container on one host's bridge network with optional NanoCPU/memory caps
(agent.go:482-508). Here, placement is the core TPU question: which chips of
the slice an agent's engine binds, and how much HBM it may claim for weights
+ KV. The scheduler is the source of the device mesh each engine builds.

Model: a slice is ``total_chips`` chips (e.g. v5e-8) with ``hbm_per_chip``
bytes each (16 GiB on v5e), laid out as a 2-D mesh (v5e-8 is 2×4). An
allocation is an ICI-adjacent sub-rectangle of that grid, so TP/ring
collectives ride physical neighbor links. Weight-sharing groups let several agents
serving the same model config co-locate on the same chips and count the
weight bytes once (the multi-agent HBM-sharing feature of BASELINE.json
config #4).

Allocations are persisted at ``slices:allocations`` so a restarted control
plane reconciles placement instead of double-booking chips.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

from ..core.errors import ResourceExhausted
from ..core.spec import Agent
from ..store.base import Store
from ..store.schema import Keys

HBM_PER_CHIP_V5E = 16 * 1024**3


@dataclass
class Placement:
    agent_id: str
    chips: tuple[int, ...]
    hbm_bytes: int
    share_group: str = ""  # e.g. model config name when weights are shared
    # fleet replica ordinal: replicas of a chip-backed agent are separate
    # processes, and a chip belongs to one process — each gets its own chips
    replica: int = 0

    @property
    def key(self) -> str:
        return _placement_key(self.agent_id, self.replica)

    def to_dict(self) -> dict:
        return {
            "agent_id": self.agent_id,
            "chips": list(self.chips),
            "hbm_bytes": self.hbm_bytes,
            "share_group": self.share_group,
            "replica": self.replica,
        }

    @staticmethod
    def from_dict(d: dict) -> "Placement":
        return Placement(
            agent_id=d["agent_id"],
            chips=tuple(d["chips"]),
            hbm_bytes=int(d["hbm_bytes"]),
            share_group=d.get("share_group", ""),
            replica=int(d.get("replica", 0)),
        )


def _placement_key(agent_id: str, replica: int) -> str:
    return agent_id if replica == 0 else f"{agent_id}#r{replica}"


@dataclass
class SliceTopology:
    """A TPU slice as a 2-D chip grid.

    v5e-8 is physically a 2×4 mesh, not a ring — "adjacent" means
    neighboring in the grid, and an ICI-efficient allocation is a
    sub-RECTANGLE of it (round-1's 1-D "contiguous id run" model called
    chips 3 and 4 neighbors; on the real 2×4 grid they're in different
    rows). Chip ids are row-major over ``mesh_shape``.
    """

    total_chips: int = 8
    hbm_per_chip: int = HBM_PER_CHIP_V5E
    name: str = "v5e-8"
    mesh_shape: tuple[int, int] = (2, 4)  # (rows, cols)
    # multi-host slices (e.g. v5e-16 = 2 hosts × 8 chips): chip ids are
    # row-major with each host owning a contiguous run; placements that fit
    # one host stay on ICI, cross-host spans pay DCN (parallel/dcn.py)
    hosts: int = 1

    def __post_init__(self) -> None:
        rows, cols = self.mesh_shape
        if rows * cols != self.total_chips:
            # derive the squarest grid for the chip count (the shape daemon
            # configs omit): 8→2×4, 16→4×4, 4→2×2; primes degenerate to a row
            r = max(d for d in range(1, int(self.total_chips**0.5) + 1)
                    if self.total_chips % d == 0)
            self.mesh_shape = (r, self.total_chips // r)
        if self.hosts < 1 or self.total_chips % self.hosts:
            raise ValueError(
                f"hosts={self.hosts} must divide total_chips={self.total_chips}"
            )

    @property
    def chips_per_host(self) -> int:
        return self.total_chips // self.hosts

    def host_of(self, chip: int) -> int:
        return chip // self.chips_per_host

    def spans_hosts(self, chips: tuple[int, ...]) -> bool:
        return len({self.host_of(c) for c in chips}) > 1

    def windows(self, n: int) -> list[tuple[int, ...]]:
        """Candidate ICI-adjacent chip sets of size n, preference-ordered.

        Sub-rectangles of the grid (squarer first — shorter worst-case
        ICI hop for TP all-reduces / ring collectives), deduplicated. If
        no h×w rectangle has area n (e.g. n=3 on 2×4 → the 1×3 row run IS
        a rectangle; n=5 has none), fall back to row-major id runs so odd
        requests still place (with a wraparound hop the caller accepted
        by asking for a non-rectangular count)."""
        rows, cols = self.mesh_shape
        shapes = [
            (h, w)
            for h in range(1, rows + 1)
            for w in range(1, cols + 1)
            if h * w == n
        ]
        shapes.sort(key=lambda s: (max(s), s[0]))
        out: list[tuple[int, ...]] = []
        seen: set[tuple[int, ...]] = set()
        for h, w in shapes:
            for r in range(rows - h + 1):
                for c in range(cols - w + 1):
                    win = tuple(
                        sorted(
                            rr * cols + cc
                            for rr in range(r, r + h)
                            for cc in range(c, c + w)
                        )
                    )
                    if win not in seen:
                        seen.add(win)
                        out.append(win)
        if not out:
            out = [
                tuple(range(s, s + n)) for s in range(self.total_chips - n + 1)
            ]
        # host-aware preference: windows inside one host's ICI domain rank
        # ahead of ones whose collectives would cross DCN (stable sort
        # keeps the squareness ordering within each class)
        if self.hosts > 1:
            out.sort(key=self.spans_hosts)
        return out


class SliceScheduler:
    """First-fit contiguous chip allocator with per-chip HBM accounting."""

    def __init__(self, store: Store, topology: SliceTopology | None = None):
        self._store = store
        self.topology = topology or SliceTopology()
        self._lock = threading.RLock()
        self._placements: dict[str, Placement] = {}
        self._load()

    # -- persistence -----------------------------------------------------
    def _load(self) -> None:
        raw = self._store.get_json(Keys.SLICE_ALLOCATIONS)
        if raw:
            self._placements = {
                p.key: p for p in (Placement.from_dict(d) for d in raw)
            }

    def _save(self) -> None:
        self._store.set_json(
            Keys.SLICE_ALLOCATIONS, [p.to_dict() for p in self._placements.values()]
        )

    # -- accounting ------------------------------------------------------
    def _chip_usage(self) -> dict[int, int]:
        """HBM bytes claimed per chip, counting each share group's weights once.

        Within a share group, every member ships the same weights, so the
        group's HBM claim per chip is max(member claims), not the sum.
        """
        by_group: dict[str, list[Placement]] = {}
        solo: list[Placement] = []
        for p in self._placements.values():
            if p.share_group:
                by_group.setdefault(p.share_group, []).append(p)
            else:
                solo.append(p)
        usage: dict[int, int] = {c: 0 for c in range(self.topology.total_chips)}
        for p in solo:
            per_chip = p.hbm_bytes // max(1, len(p.chips))
            for c in p.chips:
                usage[c] += per_chip
        for group in by_group.values():
            chips: set[int] = set()
            for p in group:
                chips.update(p.chips)
            per_chip = max(p.hbm_bytes // max(1, len(p.chips)) for p in group)
            for c in chips:
                usage[c] += per_chip
        return usage

    # -- API -------------------------------------------------------------
    def allocate(self, agent: Agent, share_group: str = "", replica: int = 0) -> Placement:
        """Place ``agent`` (or its ``replica``-th fleet replica).

        A non-empty ``share_group`` marks a chip-backed engine: members of
        one group co-locate (one host process, weights counted once), while
        chips held by a DIFFERENT group are off limits — two processes
        cannot open one chip. Replicas of an agent are separate processes
        by design, so replica i > 0 forms its own group and avoids the
        chips of the agent's other replicas."""
        with self._lock:
            key = _placement_key(agent.id, replica)
            if key in self._placements:
                return self._placements[key]
            n = max(1, agent.resources.chips)
            if n > self.topology.total_chips:
                raise ResourceExhausted(
                    f"requested {n} chips but slice {self.topology.name} has "
                    f"{self.topology.total_chips}"
                )
            if share_group and replica:
                share_group = f"{share_group}#r{replica}"
            need_per_chip = agent.resources.hbm_bytes // n
            usage = self._chip_usage()
            taken: set[int] = set()
            if share_group:
                taken = {
                    c
                    for p in self._placements.values()
                    if p.share_group and p.share_group != share_group
                    for c in p.chips
                }

            def place(chips: tuple[int, ...], group: str) -> Placement:
                placement = Placement(
                    agent.id, chips, agent.resources.hbm_bytes, group, replica
                )
                self._placements[key] = placement
                self._save()
                return placement

            # Weight sharing: prefer the chips the share group already owns —
            # but only if raising the group's per-chip claim still fits
            # (usage already counts the group at its current max).
            if share_group:
                members = [p for p in self._placements.values() if p.share_group == share_group]
                group_chips = sorted({c for p in members for c in p.chips})
                if len(group_chips) >= n:
                    chips = tuple(group_chips[:n])
                    current_claim = max(
                        (p.hbm_bytes // max(1, len(p.chips)) for p in members), default=0
                    )
                    delta = max(0, need_per_chip - current_claim)
                    if all(usage[c] + delta <= self.topology.hbm_per_chip for c in chips):
                        return place(chips, share_group)
                    # group chips can't absorb the larger claim: place solo
                    # (weights not shared rather than silently overcommitted)
                    share_group = ""

            # First-fit over ICI-adjacent windows (sub-rectangles of the
            # 2-D chip grid, squarer first — see SliceTopology.windows).
            for window in self.topology.windows(n):
                if taken.isdisjoint(window) and all(
                    usage[c] + need_per_chip <= self.topology.hbm_per_chip for c in window
                ):
                    return place(window, share_group)
            raise ResourceExhausted(
                f"no ICI-adjacent {n}-chip window with {need_per_chip} B free HBM per chip "
                f"on {self.topology.name} ({self.topology.mesh_shape[0]}x"
                f"{self.topology.mesh_shape[1]} mesh)"
                + (f"; chips {sorted(taken)} belong to other engine processes" if taken else "")
            )

    def release(self, agent_id: str) -> None:
        """Drop every placement of the agent, its replicas' included."""
        with self._lock:
            keys = [k for k, p in self._placements.items() if p.agent_id == agent_id]
            for k in keys:
                del self._placements[k]
            if keys:
                self._save()

    def placement(self, agent_id: str, replica: int = 0) -> Placement | None:
        with self._lock:
            return self._placements.get(_placement_key(agent_id, replica))

    def placements(self) -> list[Placement]:
        with self._lock:
            return list(self._placements.values())

    def free_hbm(self) -> dict[int, int]:
        with self._lock:
            usage = self._chip_usage()
            return {c: self.topology.hbm_per_chip - u for c, u in usage.items()}
