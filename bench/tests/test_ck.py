"""The published model with C_k (``agcn2s-ck``, ``ck-clip``): the plain
reference against a numpy rendering of the published equations and
against the program, C_k's hand count, the check biting at the cell's
limit, and a run of the ``clip_ck`` kind through ``run_cell`` on the CPU."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchlib import faults, layout, program, reference, reference_ck
from benchlib import traffic, work, work_ck
from benchlib.cells import judge, run_cell
from test_reference import TINY

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def conf(backend="reference"):
    model = dict(TINY, use_ck=True, ck_form="clip", prune_channel_fracs=[],
                 cavity_pattern="", input_skip=1)
    return {"name": "tiny-ck", "base": "agcn-2s", "model": model,
            "quant": False, "backend": backend,
            "numerics": {"storage": "float32", "matmul_operands": "float32"}}


def test_ck_graph_matches_the_published_equations():
    """C_k[v, w] = softmax_v(sum_{c,t} theta[c,t,v] phi[c,t,w] / (Ce T)),
    rendered in numpy in the code's (N, C, T, V) layout; the reference
    holds its transpose."""
    model = conf()["model"]
    pb = reference_ck.make_ck_params(model, jax.random.PRNGKey(5))[1]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (2, 7, 25, 8)))
    got = np.asarray(reference_ck.ck_graph(x, pb, reference.EXACT))
    p = {k: np.asarray(v, np.float64) for k, v in pb.items()}
    xc = np.transpose(x, (0, 3, 1, 2)).astype(np.float64)     # N C T V
    N, C, T, V = xc.shape
    for k in range(3):
        a1 = np.einsum("ce,nctv->netv", p["theta"][k], xc) \
            + p["theta_b"][k][:, None, None]
        a2 = np.einsum("ce,nctv->netv", p["phi"][k], xc) \
            + p["phi_b"][k][:, None, None]
        ce = a1.shape[1]
        s = a1.transpose(0, 3, 1, 2).reshape(N, V, ce * T) \
            @ a2.reshape(N, ce * T, V) / (ce * T)
        s = np.exp(s - s.max(axis=1, keepdims=True))
        s = s / s.sum(axis=1, keepdims=True)                  # over v
        np.testing.assert_allclose(got[:, k], np.swapaxes(s, 1, 2),
                                   atol=1e-5)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_clip_logits_match_the_program(backend):
    from repro.train.steps import make_gcn_infer_step

    c = conf(backend)
    model = c["model"]
    cfg = program.model_config(c)
    params2 = reference_ck.make_stream_params(model, 2 ** 31 + 5)
    plans = program.build_plans(cfg, params2, c)
    x = traffic.clip_batch(jax.random.PRNGKey(3), 4, 32, 25, 3)
    got = np.asarray(jax.jit(make_gcn_infer_step(cfg))(plans, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_ck.clip_logits(params2, x, model, False))
    assert np.max(np.abs(got - want)) < 1e-4


def test_ck_hand_count():
    model = dict(TINY, gcn_channels=[64], gcn_strides=[1], gcn_frames=300,
                 prune_channel_fracs=[], cavity_pattern="", input_skip=1)
    V, K, T, ce = 25, 3, 300, 16
    ops = work_ck.ck_ops_per_row(model)
    assert ops["proj"] == 2 * K * 2 * T * V * 3 * ce     # theta_k, phi_k
    assert ops["sim"] == K * 2 * V * V * ce * T
    assert work_ck.sim_bytes_per_row(model) == 4 * K * (2 * V * ce * T
                                                        + V * V)
    assert work_ck.graph_bytes_per_row(model) == 4 * K * V * V
    assert work_ck.model_ops_per_row(model) == \
        work.model_ops_per_row(model) + ops["proj"] + ops["sim"]
    w = work_ck.window_work(model, 2, 3.0, 1)
    base = work.window_work(model, 2, 3.0, 1)["sconv"]
    assert w["sconv_rows"]["ops"] == base["ops"]
    assert w["sconv_rows"]["bytes"] == base["bytes"] + 2 * 3 * 4 * K * V * V
    assert w["ck"]["ops"] == 2 * 3 * ops["sim"]


@pytest.fixture(scope="module")
def published_readings():
    """One step of the ck-clip cell (32 rows at the published widths):
    the reference in the stated numerics, and beside it the control
    (bfloat16 storage), C_k left out and C_k softmaxed over the output
    joint, each in the stated numerics but the control."""
    c = load("configs", "agcn2s-ck.json")
    model = c["model"]
    params2 = reference_ck.make_stream_params(model, 11)
    x = traffic.clip_batch(jax.random.PRNGKey(11), 32, model["gcn_frames"],
                           25, 3)
    st, lo = reference.stated(c), reference.control(c)
    ck = jax.jit(lambda p, x, num, axis: reference_ck.clip_logits(
        p, x, model, False, num, axis), static_argnums=(2, 3))
    return {"want": np.asarray(ck(params2, x, st, -1)),
            "control": np.asarray(ck(params2, x, lo, -1)),
            "wrong_axis": np.asarray(ck(params2, x, st, -2)),
            "no_ck": np.asarray(jax.jit(lambda p, x: reference.clip_logits(
                p, x, model, False, st))(params2, x))}


@pytest.mark.parametrize("program_", ["control", "wrong_axis", "no_ck"])
def test_check_bites_at_the_ck_clip_limit(published_readings, program_):
    r = published_readings
    assert not judge(r[program_], r["want"],
                     load("limits", "ck-clip.json"), {})["correct"]


def run(backend, seconds=1.0):
    tr = {"kind": "clip_ck", "clips_per_batch": 2, "persons": 2,
          "batches": 2}
    return run_cell(conf(backend), tr, load("limits", "ck-clip.json"),
                    2 ** 31 + 99, seconds, False, "", lambda m: None,
                    time.monotonic())


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_sound_run_is_correct(backend):
    res = run(backend)
    assert res["correct"], res["compared"]
    assert res["compared"]["logit_gap"]["value"] < 1e-4


@pytest.mark.parametrize("fault", ["answer", "half"])
def test_broken_step_reads_incorrect(monkeypatch, fault):
    faults.plant(fault, monkeypatch.setattr)
    res = run("reference")
    assert not res["correct"]


def test_families_name_the_ck_kernels():
    from benchlib.kinds import clip_ck

    assert {"ck_proj", "ck_sim", "graph_sconv_rows"} <= set(clip_ck.FAMILIES)
    assert "graph_sconv" not in clip_ck.FAMILIES
    assert layout.blocks(conf()["model"])[0].cout // 4 == 2
