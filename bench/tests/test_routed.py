"""The routed open-loop kind (``router4-live``) runs to a correct result on
four CPU devices at a tiny size: four replicas, one per device, behind the
router.  In its own process, since the device count is fixed when JAX
starts."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax
from benchlib.cells import run_cell
from test_reference import conf
tr = {{"kind": "routed_open_loop", "replicas": 4, "sessions": 8,
       "frame_hz": 30.0, "qos": "fifo", "lead_in_s": 1.6,
       "wait_for_window": True, "drain_s": 10.0, "reads_per_session": 2}}
res = run_cell(conf(), tr, {{"logit_gap": 0.03}}, 2 ** 31 + 7, 1.0, False,
               "", lambda m: None, time.monotonic())
print(json.dumps({{"devices": len(jax.devices()), "correct": res["correct"],
                  "compared": res["compared"], "e2e": res["e2e"]}}))
"""


def test_routed_open_loop_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(bench=BENCH, tests=os.path.join(BENCH, "tests"),
                         src=os.path.join(os.path.dirname(BENCH), "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["correct"], res["compared"]
    assert res["compared"]["reads_missing"]["value"] == 0
    assert "frame_p95_ms" in res["e2e"]


def test_routed_service_places_one_replica_per_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = """
import sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax, jax.numpy as jnp, numpy as np
from benchlib import program, reference, traffic
from benchlib.kinds import routed_open_loop
from test_reference import conf
c = conf()
cfg = program.model_config(c)
params2 = reference.make_stream_params(c["model"], 3)
plans = program.build_plans(cfg, params2, c)
x = traffic.SessionFrames(3, 2, 25, 3, 30.0).clips(np.arange(2), 32)
stats = program.calibrate(plans, jnp.asarray(x))
svc = routed_open_loop.build(cfg, plans, stats, 8, "fifo", 4)
hs = [svc.open_session() for _ in range(8)]
print(sorted(svc.router.replica_of(h) for h in hs))
print(sorted({{d.id for s in svc.router.services
              for d in s.mesh.devices.flat}}))
""".format(bench=BENCH, tests=os.path.join(BENCH, "tests"),
           src=os.path.join(os.path.dirname(BENCH), "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    placed, devices = out.stdout.strip().splitlines()[-2:]
    assert placed == str([0, 0, 1, 1, 2, 2, 3, 3])
    assert devices == str([0, 1, 2, 3])
