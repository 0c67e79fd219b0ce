"""Elastic runtime integration tests.

These need multiple devices, so they run the training driver in a
subprocess with ``--xla_force_host_platform_device_count=8`` (the test
process itself keeps 1 device, per the dry-run isolation rule).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_shrink_keeps_queue_and_scheduler_accounting_in_agreement():
    """Regression (control plane only, no jax bind): ``shrink`` used to
    release chips directly against the scheduler without notifying any
    queue accounting.  Ported onto the Instance facade, every grow and
    shrink flows through the queue, so the queue's job record, the
    scheduler allocation, and QueueStats utilization must agree after
    each elasticity event."""
    from repro.core import EventType, Instance
    from repro.core.graph import build_tpu_fleet
    from repro.runtime.elastic import ElasticRuntime

    class ControlPlaneOnly(ElasticRuntime):
        def bind(self, key=None):       # data plane stubbed out
            pass

    fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=4,
                            chips_per_node=4)
    api = Instance(graph=fleet, name="top")
    rt = ControlPlaneOnly.__new__(ControlPlaneOnly)
    # constructor builds model configs we don't need; wire by hand
    rt.api = api
    rt.scheduler = api.scheduler
    rt.handle = None
    rt.jobid = "train-job"
    rt.chip_type = "chip"
    rt.model_axis = 1
    rt.events = []
    rt.n_resizes = 0

    def agree():
        job = api.queue.get(rt.jobid)
        alloc = api.scheduler.allocations[rt.jobid]
        assert sorted(job.paths) == sorted(alloc.paths)
        busy = sum(len(j.paths) for j in api.queue.running)
        assert busy == len(job.paths)

    assert rt.allocate(4)
    agree()
    assert rt.grow(4)
    assert rt.chips_allocated() == 8
    agree()
    assert rt.shrink(2)
    assert rt.chips_allocated() == 6
    agree()
    # shrink below the model axis floor is refused, accounting intact
    assert not rt.shrink(6)
    assert rt.chips_allocated() == 6
    agree()
    # events flowed back through the journal: grow and shrink are
    # observable, first-class operations
    kinds = [e.type for e in api.events.for_job(rt.jobid)]
    assert EventType.GROW in kinds and EventType.SHRINK in kinds


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


RESHARD = """
import jax, numpy as np
from jax._src import array
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh_for
from repro.runtime.elastic import reshard

spec, model_axis = P(*{spec!r}), {model_axis}
fetches, compiles = [], []
value = array.ArrayImpl._value
array.ArrayImpl._value = property(
    lambda self: fetches.append(1) or value.fget(self))
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, d, **_: compiles.append(ev)
    if ev == "/jax/core/compile/backend_compile_duration" else None)

want = {{"w": np.arange(8 * 12 * 3, dtype=np.float32).reshape(8, 12, 3),
        "b": np.arange(8, dtype=np.int32), "step": np.int32(7)}}
specs = {{"w": spec, "b": P(*spec[:1]), "step": P()}}

def on(mesh):
    return {{k: NamedSharding(mesh, s) for k, s in specs.items()}}

small, big = make_mesh_for(2, model_axis), make_mesh_for(4, model_axis)
state = jax.device_put(want, on(small))
moves = {{}}
for rnd in range(2):
    n0 = len(compiles)
    for mesh in (big, small):
        f0 = len(fetches)
        state, copied, fallback = reshard(state, on(mesh), moves)
        assert len(fetches) == f0, "staged through the host"
        assert fallback == 0 and (copied > 0 or mesh is small), copied
        for k, x in state.items():
            assert x.sharding == on(mesh)[k], (k, x.sharding)
            assert x.devices() == set(mesh.devices.flat), (k, x.devices())
            assert np.asarray(x).tobytes() == want[k].tobytes(), k
    if rnd:
        assert len(compiles) == n0, "a warm move compiled"

# a move onto equivalent shardings copies nothing
same, copied, fallback = reshard(state, on(small), moves)
assert copied == fallback == 0 and same["w"].sharding == on(small)["w"]
print("RESHARD_OK")
"""


@pytest.mark.parametrize("spec,model_axis", [
    (("data",), 1), ((None, "data"), 1), ((), 1), (("data", "model"), 2)])
def test_reshard_moves_shard_to_shard(spec, model_axis):
    """A 2 -> 4 -> 2 device move of the training state's layouts goes
    shard to shard: bit-exact values, the target sharding on exactly the
    target mesh's devices, no host fetch (``ArrayImpl._value``), nothing
    through the ``device_put`` fallback, and a second grow and shrink
    compile nothing."""
    out = _run(RESHARD.format(spec=spec, model_axis=model_axis), devices=4)
    assert "RESHARD_OK" in out


@pytest.mark.slow
def test_grow_shrink_fail_loop(tmp_path):
    out = _run(f"""
from repro.launch.train import run_training
res = run_training("llama3.2-3b", steps=12, smoke=True,
                   grow_at=3, shrink_at=6, fail_at=9,
                   ckpt_dir={str(tmp_path)!r}, ckpt_every=5)
kinds = [e.kind for e in res["events"]]
assert "grow" in kinds and "shrink" in kinds and "eject" in kinds, kinds
import numpy as np
assert np.isfinite(res["losses"]).all()
print("ELASTIC_OK", kinds)
""")
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_checkpoint_restart_resumes(tmp_path):
    """Kill-and-restart: restore from checkpoint onto a DIFFERENT device
    count and keep training (topology-independent checkpoints)."""
    out = _run(f"""
import jax
from repro.launch.train import run_training
from repro.runtime.checkpoint import CheckpointManager
res = run_training("llama3.2-3b", steps=11, smoke=True,
                   ckpt_dir={str(tmp_path)!r}, ckpt_every=10)
print("PHASE1_OK")
""", devices=8)
    assert "PHASE1_OK" in out
    out = _run(f"""
import jax
from repro.configs.registry import get_config
from repro.core.graph import build_tpu_fleet
from repro.core.scheduler import SchedulerInstance
from repro.models.config import ShapeConfig
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.elastic import ElasticRuntime
from repro.data.pipeline import SyntheticTokenPipeline

cfg = get_config("llama3.2-3b").reduced()
shape = ShapeConfig("smoke_train", 32, 8, "train")
fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=1,
                        chips_per_node=4)
sched = SchedulerInstance("top", fleet)
rt = ElasticRuntime(sched, cfg, shape, chip_type="chip")
assert rt.allocate(4)
rt.bind(jax.random.key(0))
mgr = CheckpointManager({str(tmp_path)!r})
step, state = mgr.restore(
    like={{"params": rt.params, "opt_state": rt.opt_state}},
    shardings={{"params": rt.model.param_shardings(),
               "opt_state": rt.model.opt_shardings()}})
rt.params, rt.opt_state = state["params"], state["opt_state"]
pipe = SyntheticTokenPipeline(cfg, shape)
m = rt.step(pipe.batch_at(step))
import numpy as np
assert np.isfinite(float(m["loss"]))
assert step >= 10
print("RESTORE_OK", step, float(m["loss"]))
""", devices=4)
    assert "RESTORE_OK" in out


@pytest.mark.slow
def test_straggler_ejection():
    out = _run("""
import jax
from repro.configs.registry import get_config
from repro.core.graph import build_tpu_fleet
from repro.core.scheduler import SchedulerInstance
from repro.models.config import ShapeConfig
from repro.runtime.elastic import ElasticRuntime
from repro.runtime.straggler import StragglerPolicy

cfg = get_config("llama3.2-3b").reduced()
shape = ShapeConfig("s", 32, 8, "train")
fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=4,
                        chips_per_node=4)
sched = SchedulerInstance("top", fleet)
rt = ElasticRuntime(sched, cfg, shape, chip_type="chip")
assert rt.allocate(8)
rt.bind(jax.random.key(0))
pol = StragglerPolicy(rt)
# derive the nodes actually backing the allocation
g = sched.graph
nodes = sorted({next(a for a in g.ancestors(p)
                     if g.vertex(a).type == "node")
                for p in sched.allocations[rt.jobid].paths
                if g.vertex(p).type == "chip"})
assert len(nodes) >= 2
for i in range(4):
    pol.record_and_act({nodes[0]: 1.0, nodes[1]: 5.0})
assert nodes[1] in pol.ejected, pol.ejected
assert rt.chips_allocated() == 8, rt.chips_allocated()
print("STRAGGLER_OK")
""", devices=8)
    assert "STRAGGLER_OK" in out


@pytest.mark.slow
def test_compressed_psum_accuracy():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.compress import compressed_psum, quantize_int8, dequantize_int8
mesh = jax.make_mesh((2, 2), ("pod", "data"))
g = {"w": jax.random.normal(jax.random.key(0), (64, 64))}
out = compressed_psum(g, jax.random.key(1), mesh, axis="pod")
# replicated input: psum/n == identity up to quantization error
err = float(jnp.abs(out["w"] - g["w"]).max())
rng = float(jnp.abs(g["w"]).max())
assert err < 0.02 * rng, (err, rng)
# quantize roundtrip error bounded by scale
q, s = quantize_int8(g["w"], jax.random.key(2))
err2 = float(jnp.abs(dequantize_int8(q, s) - g["w"]).max())
assert err2 <= float(s.max()) + 1e-6
print("COMPRESS_OK", err)
""", devices=4)
    assert "COMPRESS_OK" in out


def test_elastic_spans_and_counters():
    """With a collector on the scheduler, a grow records ``elastic.grow``
    holding the queue's ``match_grow`` and the rebind (``elastic.bind``
    > ``elastic.reshard``), a shrink ``elastic.shrink``; a size already
    bound builds no step.  Detached, nothing is recorded; the counters
    count every resize either way (one host device: every mesh is 1)."""
    import jax

    from repro.configs.registry import get_config
    from repro.core import Instance, SchedulerInstance, SpanCollector
    from repro.core.graph import build_tpu_fleet
    from repro.models.config import ShapeConfig
    from repro.runtime.elastic import ElasticRuntime

    fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=1,
                            chips_per_node=4)
    rt = ElasticRuntime(Instance(SchedulerInstance("host", fleet)),
                        get_config("musicgen-medium").reduced(),
                        ShapeConfig("t", 16, 2, "train", cond_len=8),
                        chip_type="chip")
    assert rt.allocate(2)
    rt.bind(jax.random.key(0))
    col = SpanCollector()
    assert rt.grow(2) and rt.shrink(2)
    assert col.recorded == 0 and rt.n_resizes == 2

    rt.scheduler.span_collector = col
    assert rt.grow(2) and rt.shrink(2)
    spans = col.drain()
    by_id = {s["id"]: s for s in spans}

    def parent(s):
        return by_id[s["parent"]]["name"] if s["parent"] else None
    names = [s["name"] for s in spans]
    assert names.count("elastic.grow") == names.count("elastic.shrink") == 1
    assert "elastic.step_build" not in names
    for s in spans:
        if s["name"] == "match_grow":
            assert parent(s) == "elastic.grow"
        if s["name"] == "elastic.bind":
            assert parent(s) in ("elastic.grow", "elastic.shrink")
        if s["name"] == "elastic.reshard":
            assert parent(s) == "elastic.bind"
    assert names.count("match_grow") == 1
    assert names.count("elastic.bind") == names.count("elastic.reshard") == 2
    assert rt.n_resizes == 4 and rt.n_step_builds == 1
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (rt.params, rt.opt_state)))
    assert rt.reshard_bytes == 4 * nbytes
    # one device: the shardings are equivalent, nothing changes chips
    assert rt.reshard_fallback_bytes == 0
    for s in spans:
        if s["name"] == "elastic.reshard":
            assert (s["bytes"], s["copied_bytes"], s["fallback_bytes"]) \
                == (nbytes, 0, 0)
