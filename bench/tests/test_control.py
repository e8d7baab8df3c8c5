"""The control at a size a test run holds: the plain reference one storage
step below the numerics a configuration states (bfloat16 for float32), put
in the program's place, reads not correct against the reference in the
stated numerics, by the harness's own judgement, at the published widths
and a few rows.  The chip readings of the same control at the cells' own
sizes are in PERF.md (``bench/readings.py``)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import layout, reference, traffic
from benchlib.cells import judge

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("config,cell", [
    ("agcn2s-pruned", "pruned-clip"), ("agcn2s-woc", "woc-clip")])
def test_clip_control_is_not_correct(config, cell):
    conf = load("configs", config + ".json")
    model = conf["model"]
    params2 = reference.make_stream_params(model, 11)
    x = traffic.clip_batch(jax.random.PRNGKey(11), 32, model["gcn_frames"],
                           25, 3)
    want, low = (reference.clip_logits(params2, x, model, conf["quant"], num)
                 for num in (reference.stated(conf),
                             reference.control(conf)))
    assert not judge(np.asarray(low), np.asarray(want),
                     load("limits", cell + ".json"), {})["correct"]


@pytest.fixture(scope="module")
def stream_readings():
    """16 sessions of 320 frames, each read 4 times, in the stated numerics
    and in the control's."""
    conf = load("configs", "agcn2s-pruned.json")
    model = conf["model"]
    params2 = reference.make_stream_params(model, 21)
    src = traffic.SessionFrames(21, 24, 25, 3, 30.0)
    xc = jnp.asarray(src.clips(np.arange(16, 24), model["gcn_frames"]))
    frames = jnp.asarray(src.clips(np.arange(16), 320))
    rows = jnp.asarray(np.repeat(np.arange(16), 4))
    done = jnp.asarray([layout.emitted(model, n)
                        for n in np.tile([200, 240, 280, 320], 16)])
    out = []
    for num in (reference.stated(conf), reference.control(conf)):
        stats = reference.calibrate(params2, xc, model, True, num)
        feats = reference.stream_features(params2, stats, frames, model,
                                          True, num)
        out.append(np.asarray(reference.read_logits(params2, feats, rows,
                                                    done, num)))
    return out


@pytest.mark.parametrize("cell", ["pruned-live", "pruned-backlog"])
def test_stream_control_is_not_correct(stream_readings, cell):
    want, low = stream_readings
    assert not judge(low, want, load("limits", cell + ".json"),
                     {})["correct"]
