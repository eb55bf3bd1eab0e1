"""Heterogeneous-MP fleets: spec authority, meshed parity, cross-degree migration,
and live split/merge reconfiguration.

Runs in two CI environments: the plain tier-1 suite (one device — every worker
falls back un-meshed, the control plane still prices declared degrees) and a
dedicated job under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
where mp>1 workers are physically sharded on carved sub-meshes and the parity
tests exercise real cross-shard numerics.
"""

import jax
import pytest

from repro.configs import get_config
from repro.engine.fleet import FleetSpec, RolloutFleet
from repro.engine.runtime import RuntimeConfig, build_workbench, make_runtime
from repro.engine.sampler import SamplerConfig
from repro.engine.worker import RolloutWorker
from repro.launch.mesh import carve_worker_meshes
from repro.models import model as M

GREEDY = SamplerConfig(temperature=0.0)
PROMPT = [5, 6, 7, 8, 9, 10]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3_1_7b").reduced(n_periods=1)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mesh(mp: int):
    """A real sub-mesh when the host has the devices, else None (fallback)."""
    if mp > 1 and jax.device_count() >= mp:
        return carve_worker_meshes([mp], jax.devices()[:mp])[0]
    return None


# ---------------------------------------------------------------- FleetSpec

def test_fleet_spec_validates_order_and_degrees():
    spec = FleetSpec((4, 2, 1, 1))
    assert spec.n_workers == 4
    assert spec.budget == 8
    with pytest.raises(ValueError):
        FleetSpec((1, 2, 4))                  # ascending: breaks sort-and-zip
    with pytest.raises(ValueError):
        FleetSpec((2, 0))
    with pytest.raises(ValueError):
        FleetSpec(())
    assert FleetSpec.from_degrees([1, 4, 2]).degrees == (4, 2, 1)
    assert FleetSpec.homogeneous(3).degrees == (1, 1, 1)


def test_fleet_spec_from_allocation():
    from repro.core.placement import InterferenceModel
    from repro.core.resource_manager import sort_initialized_sa
    res = sort_initialized_sa([400.0, 90.0, 40.0, 10.0], budget=8,
                              interference=InterferenceModel.analytic(0.05),
                              seed=0)
    spec = FleetSpec.from_allocation(res)
    assert spec.budget == 8
    assert list(spec.degrees) == sorted(spec.degrees, reverse=True)


def test_carve_worker_meshes_falls_back_without_devices():
    # off-TPU, a device list too small for the budget degrades every worker to
    # un-meshed (the declared degrees still drive the control plane)
    meshes = carve_worker_meshes([4, 2, 1, 1], jax.devices()[:1])
    assert meshes == [None] * 4
    # several mp1 workers on one device share it; a lone mp1 worker is covered
    assert carve_worker_meshes([1, 1], jax.devices()[:1]) == [None, None]
    [lone] = carve_worker_meshes([1], jax.devices()[:1])
    assert lone.devices.shape == (1, 1) and lone.devices.flat[0] == jax.devices()[0]


def test_carve_worker_meshes_refuses_oversized_fleet_on_tpu():
    """On a TPU an mp>1 fleet that does not fit would run silently unsharded."""
    from types import SimpleNamespace
    chip = SimpleNamespace(platform="tpu")
    with pytest.raises(ValueError, match="need 3 devices"):
        carve_worker_meshes([2, 1], [chip])
    assert carve_worker_meshes([1, 1], [chip]) == [None, None]   # shared chip


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 host devices")
def test_carve_worker_meshes_gives_mp1_workers_their_own_devices():
    meshes = carve_worker_meshes([1, 1], jax.devices()[:2])
    assert [m.devices.shape for m in meshes] == [(1, 1), (1, 1)]
    assert {m.devices.flat[0].id for m in meshes} == {d.id for d in jax.devices()[:2]}


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_carve_worker_meshes_disjoint_blocks():
    meshes = carve_worker_meshes([4, 2, 1, 1], jax.devices())
    assert [m.devices.shape for m in meshes] == [(1, 4), (1, 2), (1, 1), (1, 1)]
    blocks = [{d.id for d in m.devices.flat} for m in meshes]
    assert len(set().union(*blocks)) == 8     # disjoint: all 8 chips, no overlap


# ------------------------------------------------- cross-degree data plane

@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 host devices")
def test_meshed_decode_matches_unmeshed(setup):
    """MP sharding must not change the sampled token stream (same worker_id)."""
    cfg, params = setup
    meshed = RolloutWorker(cfg, params, capacity=32, max_slots=2,
                           mesh=_mesh(2), mp=2)
    plain = RolloutWorker(cfg, params, capacity=32, max_slots=2)
    assert meshed.mesh is not None
    for w in (meshed, plain):
        w.prefill(0, PROMPT)
    assert meshed.decode([0], 16)[0] == plain.decode([0], 16)[0]


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 host devices")
def test_meshed_pallas_decode_matches_unmeshed(setup):
    """The Pallas decode kernel runs per KV-head shard under an mp=2 mesh
    (shard_map; interpret mode off-TPU) and decodes the unmeshed tokens."""
    from dataclasses import replace
    cfg, params = setup
    cfg = replace(cfg, use_pallas_decode=True)
    meshed = RolloutWorker(cfg, params, capacity=32, max_slots=2, sampler=GREEDY,
                           mesh=_mesh(2), mp=2)
    plain = RolloutWorker(cfg, params, capacity=32, max_slots=2, sampler=GREEDY)
    for w in (meshed, plain):
        w.prefill(0, PROMPT)
    assert meshed.decode([0], 8)[0] == plain.decode([0], 8)[0]


def test_cross_degree_migration_parity(setup):
    """Preempt on mp=2 -> migrate -> resume on mp=1 decodes the tokens an
    unmigrated run would have (§5.3 + §6: migration crosses MP degrees)."""
    cfg, params = setup
    src = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=0,
                        sampler=GREEDY, mesh=_mesh(2), mp=2)
    ref = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=0,
                        sampler=GREEDY, mesh=_mesh(2), mp=2)
    dst = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=1,
                        sampler=GREEDY, mp=1)
    src.prefill(7, PROMPT)
    ref.prefill(7, PROMPT)
    straight = ref.decode([7], 12)[7]
    first = src.decode([7], 6)[7]
    src.preempt(7)
    pkg = src.migrate_out(7)
    assert pkg["preempted"]                   # lifecycle flag travels
    dst.migrate_in(pkg)
    resumed = dst.decode([7], 6)[7]           # implicit resume on the mp=1 pool
    assert first + resumed == straight
    assert dst.store[7].tokens == ref.store[7].tokens


def test_cross_degree_migration_roundtrip_low_to_high(setup):
    """mp=1 -> mp=4 implant also holds (re-shard on ingress, not egress)."""
    cfg, params = setup
    src = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=0,
                        sampler=GREEDY, mp=1)
    ref = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=0,
                        sampler=GREEDY, mp=1)
    dst = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=1,
                        sampler=GREEDY, mesh=_mesh(4), mp=4)
    src.prefill(3, PROMPT)
    ref.prefill(3, PROMPT)
    straight = ref.decode([3], 10)[3]
    first = src.decode([3], 5)[3]
    dst.migrate_in(src.migrate_out(3))
    assert first + dst.decode([3], 5)[3] == straight


# ---------------------------------------------------- fleet spec authority

def _tiny_runtime(cfg, params, fleet=None, n_workers=2, seed=11):
    batch, predictor = build_workbench(n_prompts=2, group_size=2, seed=seed,
                                       max_steps=1, base_steps=1.0)
    rcfg = RuntimeConfig(scheduler="pps", migration=False, max_active=1,
                         quantum=8, seed=seed)
    return make_runtime(cfg, params, batch, predictor, n_workers=n_workers,
                        config=rcfg, fleet=fleet)


def test_fleet_spec_is_single_source_of_truth(setup):
    """Regression: runtime used to lazily re-stub controller.degrees = [1]*n in
    two places; a stale stub could silently mask a real allocation.  Now the
    fleet spec is authoritative and any drift fails loudly."""
    cfg, params = setup
    rt = _tiny_runtime(cfg, params, fleet=FleetSpec((2, 1, 1)))
    assert rt.controller.degrees == [2, 1, 1]     # synced at construction
    rt.controller.degrees = [1, 1, 1]             # the old stub, now poison
    with pytest.raises(ValueError, match="drifted"):
        rt.run()


def test_runtime_rejects_conflicting_preset_degrees(setup):
    cfg, params = setup
    rt = _tiny_runtime(cfg, params, fleet=FleetSpec((2, 1)))
    from repro.engine.runtime import RolloutRuntime, ToolEnvironment
    ctrl = rt.controller
    ctrl.degrees = [1, 1]                         # stale stub pre-set by caller
    with pytest.raises(ValueError, match="single source of truth"):
        RolloutRuntime(rt.fleet, ctrl, rt.trajs, ToolEnvironment(), rt.cfg)


def test_heterogeneous_degrees_change_virtual_pricing(setup):
    """The stub's disappearance is observable: per-worker token times now come
    from the latency model, so a het fleet prices decode differently."""
    cfg, params = setup
    rt = _tiny_runtime(cfg, params, fleet=FleetSpec((4, 1)))
    times = [ws.token_time for ws in rt.workers]
    assert times[0] < times[1]                    # mp=4 decodes faster
    assert times[1] == pytest.approx(rt.cfg.token_time)


# ------------------------------------------------------------- reconfigure

def test_fleet_reconfigure_migrates_residents_across_degrees(setup):
    cfg, params = setup
    fleet = RolloutFleet(cfg, params, FleetSpec((2, 2)), capacity=32,
                         max_slots=2, sampler=GREEDY)
    ref = RolloutWorker(cfg, params, capacity=32, max_slots=2, worker_id=1,
                        sampler=GREEDY, mesh=_mesh(2), mp=2)
    fleet.workers[1].prefill(5, PROMPT)
    ref.prefill(5, PROMPT)
    first = fleet.workers[1].decode([5], 6)[5]
    keep = fleet.workers[0]
    report = fleet.reconfigure(FleetSpec((2, 1, 1)))  # split: slot 1 becomes 2x mp1
    assert report["to"] == [2, 1, 1]
    assert report["migrated_residents"] == 1
    assert 0 in report["reused"] and 1 in report["rebuilt"]
    assert fleet.workers[0] is keep               # unchanged slot is reused
    assert fleet.workers[1].mp == 1
    assert 5 in fleet.workers[1].store            # resident landed on slot 1
    resumed = fleet.workers[1].decode([5], 6)[5]
    assert first + resumed == ref.decode([5], 12)[5]


def test_fleet_reconfigure_rebuilds_on_mesh_presence_change(setup):
    """A fleet crossing in or out of the meshed regime must re-place every
    worker — reusing an un-meshed engine under a newly carved mesh would
    silently ignore the new sharding (and vice versa)."""
    cfg, params = setup
    devices = jax.devices()[:2]
    fleet = RolloutFleet(cfg, params, FleetSpec((1, 1)), capacity=32,
                         max_slots=2, sampler=GREEDY, devices=devices)
    had_meshes = len(devices) == 2                  # one device per mp1 worker
    assert all((w.mesh is not None) == had_meshes for w in fleet.workers)
    report = fleet.reconfigure(FleetSpec((2, 1)))   # needs 3 devices: un-meshed
    if any(w.mesh is not None for w in fleet.workers):
        pytest.fail("a fleet the devices cannot cover must be un-meshed")
    if had_meshes:
        assert report["rebuilt"] == [0, 1]          # both crossed out of meshes
    else:
        assert report["reused"] == [1]              # fallback: degree-only reuse


def test_runtime_reconfigure_keeps_controller_in_sync(setup):
    cfg, params = setup
    rt = _tiny_runtime(cfg, params, fleet=FleetSpec((2, 1, 1)))
    rt.run()
    report = rt.reconfigure()                     # calibrate + Algorithm 2
    assert sum(report["to"]) == 4                 # budget conserved
    assert rt.controller.degrees == list(rt.spec.degrees)
    assert rt.spec.degrees == rt.fleet.spec.degrees
    assert [w.mp for w in rt.fleet.workers] == list(rt.spec.degrees)


def test_reconfigure_requires_fleet_and_drained_queue(setup):
    cfg, params = setup
    rt = _tiny_runtime(cfg, params, n_workers=2)
    rt.fleet = None
    with pytest.raises(ValueError, match="RolloutFleet"):
        rt.reconfigure()


# ------------------------------------------------------------- calibration

def test_calibration_observations_flow_from_dispatch_stats(setup):
    cfg, params = setup
    rt = _tiny_runtime(cfg, params, fleet=FleetSpec((2, 1)))
    rt.run()
    obs = rt.controller.calibration_observations()
    assert len(obs) == 2                          # both workers reported timing
    assert {mp for mp, _, _ in obs} == {1, 2}
    assert all(t > 0.0 for _, _, t in obs)
    fitted = rt.calibrate()
    assert fitted is not None and fitted.t1 > 0.0
    assert rt.controller.latency is fitted        # next provision uses it
