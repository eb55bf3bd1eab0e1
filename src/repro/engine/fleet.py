"""Heterogeneous model-parallel worker fleets (paper §6 on the real data plane).

Two pieces close the last control/data-plane seam:

* ``FleetSpec`` — the **single source of truth** for per-worker model-parallel
  degrees.  Everything that used to guess (``controller.degrees = [1] * n``
  stubs in the runtime) now derives from one spec: the controller's degree
  vector, the per-worker virtual token times, the placement DP's sort-and-zip
  mapping (§6.1: workers descend by MP degree, partitions descend by length),
  and the physical sub-meshes the workers are built on.

* ``RolloutFleet`` — owns the live ``RolloutWorker`` set.  Construction carves
  one disjoint ``("data", "model")`` sub-mesh per worker out of the visible
  device set (``launch.mesh.carve_worker_meshes``) and shards each worker's
  params and KV pool with the MaxText-style rules in ``distributed/sharding``;
  ``reconfigure`` executes the simulated-annealing allocator's split/merge
  moves on the live fleet between rollout steps — workers whose degree survives
  are reused (their radix caches stay warm), changed slots are rebuilt on fresh
  sub-meshes (weights re-sharded), and any resident sequences of retired
  workers are migrated lane-by-lane onto the new fleet (``migrate_out`` gathers
  to host, ``migrate_in`` re-implants under the destination's sharding, so
  moves cross MP degrees).

When the device set cannot host ``sum(degrees)`` accelerators — the un-forced
CPU tier-1 environment — every worker falls back to un-meshed execution while
the *declared* degrees keep driving the control plane, so heterogeneous
scheduling remains testable on one device and becomes physically real under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (CI) or on actual pods.
On a TPU such an mp>1 fleet is an error instead (``carve_worker_meshes``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

from repro.engine.sampler import SamplerConfig
from repro.engine.worker import RolloutWorker
from repro.launch.mesh import carve_worker_meshes


@dataclass(frozen=True)
class FleetSpec:
    """Per-worker MP degrees, descending — the §6.1 sort-and-zip order."""

    degrees: tuple[int, ...]

    def __post_init__(self):
        if not self.degrees:
            raise ValueError("FleetSpec needs at least one worker")
        if any(int(d) < 1 for d in self.degrees):
            raise ValueError(f"MP degrees must be >= 1, got {self.degrees}")
        if list(self.degrees) != sorted(self.degrees, reverse=True):
            raise ValueError(
                "degrees must be descending (sort-and-zip mapping relies on "
                f"worker order == degree order), got {self.degrees}",
            )

    @property
    def n_workers(self) -> int:
        return len(self.degrees)

    @property
    def budget(self) -> int:
        """Total accelerators consumed (the Algorithm 2 budget N)."""
        return int(sum(self.degrees))

    @classmethod
    def homogeneous(cls, n_workers: int, mp: int = 1) -> "FleetSpec":
        return cls(tuple([int(mp)] * n_workers))

    @classmethod
    def from_degrees(cls, degrees: Sequence[int]) -> "FleetSpec":
        return cls(tuple(sorted((int(d) for d in degrees), reverse=True)))

    @classmethod
    def from_allocation(cls, allocation) -> "FleetSpec":
        """Adopt an AllocationResult (Algorithm 2 output) as the fleet shape."""
        return cls.from_degrees(allocation.degrees)


class RolloutFleet:
    """The live heterogeneous worker set and its between-steps reconfiguration."""

    def __init__(
        self,
        cfg,
        params,
        spec: FleetSpec,
        *,
        capacity: int,
        max_slots: int,
        sampler: SamplerConfig = SamplerConfig(),
        seed: int = 0,
        devices=None,
        **worker_kwargs,
    ):
        self.cfg = cfg
        self.params = params  # un-sharded reference copy (re-shard source)
        self.capacity = capacity
        self.max_slots = max_slots
        self.sampler = sampler
        self.seed = seed
        self.devices = devices
        self.worker_kwargs = dict(worker_kwargs)
        self.spec = spec
        self.reconfigurations = 0
        meshes = carve_worker_meshes(spec.degrees, devices)
        self.workers = []
        for i, (degree, mesh) in enumerate(zip(spec.degrees, meshes)):
            self.workers.append(self._build_worker(i, degree, mesh))

    def _build_worker(self, wid: int, degree: int, mesh) -> RolloutWorker:
        return RolloutWorker(
            self.cfg,
            self.params,
            capacity=self.capacity,
            max_slots=self.max_slots,
            worker_id=wid,
            sampler=self.sampler,
            seed=self.seed,
            mesh=mesh,
            mp=degree,
            **self.worker_kwargs,
        )

    def reconfigure(self, new_spec: FleetSpec) -> dict:
        """Realize ``new_spec`` on the live fleet (split / merge / redistribute).

        Worker slots whose degree is unchanged keep their engine (KV pool, radix
        cache, retired lanes all stay warm).  Changed or new slots get a fresh
        worker on a newly carved sub-mesh — the weight re-shard of a split/merge
        move.  Resident sequences of every retired engine are migrated onto the
        new fleet (same slot index when it exists, else the least-populated new
        worker), crossing MP degrees via the host-bounce re-implant.  Returns a
        report dict; the caller (runtime / controller) must re-sync
        ``controller.degrees`` from ``fleet.spec`` — ``FleetSpec`` stays the
        only authority.
        """
        old_spec, old_workers = self.spec, self.workers
        meshes = carve_worker_meshes(new_spec.degrees, self.devices)
        # a slot is reusable only if its degree, its mesh PRESENCE, and its
        # device block all survive: a fleet crossing in or out of the meshed
        # regime must re-place every worker (a reused un-meshed worker would
        # silently ignore its newly carved mesh), and an earlier split/merge
        # shifts every later carve offset, where a reused worker keeping its
        # old mesh would overlap a rebuilt neighbor's chips.
        old_off = [sum(old_spec.degrees[:i]) for i in range(old_spec.n_workers)]
        new_off = [sum(new_spec.degrees[:i]) for i in range(new_spec.n_workers)]
        reused = []
        workers = []
        for i, (degree, mesh) in enumerate(zip(new_spec.degrees, meshes)):
            same = i < len(old_workers) and old_spec.degrees[i] == degree
            if same:
                old_mesh = old_workers[i].mesh
                if (mesh is None) != (old_mesh is None):
                    same = False
                elif mesh is not None:
                    same = old_off[i] == new_off[i]
            if same:
                workers.append(old_workers[i])
                reused.append(i)
            else:
                workers.append(self._build_worker(i, degree, mesh))
        moves: dict[int, int] = {}  # seq_id -> destination worker index
        for i, old in enumerate(old_workers):
            if i in reused:
                continue
            for seq_id in list(old.store):
                pkg = old.migrate_out(seq_id)
                if i < len(workers):
                    dst = workers[i]
                else:  # fleet shrank past this slot: redistribute (elastic case)
                    dst = min(workers, key=lambda w: len(w.store))
                dst.migrate_in(pkg)
                moves[seq_id] = dst.worker_id
        self.spec = new_spec
        self.workers = workers
        self.reconfigurations += 1
        rebuilt = [i for i in range(new_spec.n_workers) if i not in reused]
        return {
            "from": list(old_spec.degrees),
            "to": list(new_spec.degrees),
            "reused": reused,
            "rebuilt": rebuilt,
            "migrated_residents": len(moves),
            "moves": moves,
        }
