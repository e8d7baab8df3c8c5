"""Closed-loop clip traffic on the published 2s-AGCN with its adaptive
graph C_k (``"kind": "clip_ck"``): the loop, batches and parameters of
:mod:`benchlib.kinds.clip`, on the weights and plain reference of
:mod:`benchlib.reference_ck`, with C_k's work counted by
:mod:`benchlib.work_ck`.

The timed program must hold the C_k kernels compiled (``ck_proj``,
``ck_sim``) and aggregate with the per-row ``graph_sconv_rows``.  A
traced run reads, for the per-layer metrics: the C_k kernels' device
time per step (``ck_s``), the similarity kernel's and the per-row
spatial kernel's roofline families (``ck``, ``sconv_rows``), and the
model's counted operations with C_k's.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from benchlib import layout, peaks, program, reference_ck, trace, traffic
from benchlib import work_ck
from benchlib.cells import (CompileCounter, GcWatch, Tracer, families,
                            log_setup, memory_peak, settle, span)
from benchlib.kinds import clip

CK_KERNELS = ("ck_proj", "ck_sim")
FAMILIES = tuple(f for f in clip.FAMILIES if f != "graph_sconv") + (
    "graph_sconv_rows",) + CK_KERNELS


def trace_context(red, n_steps: int, model: dict, rows_clips: float,
                  device_kind: str):
    """The readers' context (:mod:`benchlib.readers`) with C_k's terms:
    ``family_s`` gains ``ck`` (``ck_sim``) and ``sconv_rows``
    (``graph_sconv_rows``), and the counters ``ck_s``, the device seconds
    of every C_k kernel in the traced window."""
    op_s = red.get("op_s", {})
    red.setdefault("family_s", {}).update(
        ck=op_s.get("ck_sim", 0.0),
        sconv_rows=op_s.get("graph_sconv_rows", 0.0))
    counters = {"steps": n_steps,
                "ck_s": sum(op_s.get(k, 0.0) for k in CK_KERNELS),
                "model_ops": 2 * rows_clips
                * work_ck.model_ops_per_row(model)}
    return {"red": red, "counters": counters, "peak": peaks.peak(device_kind),
            "work": work_ck.window_work(model, 2, rows_clips, n_steps)}


def run(conf, tr, seed, seconds, traced, trace_dir, log, t_start):
    import jax

    model = conf["model"]
    quant = bool(conf["quant"])
    clock = time.monotonic
    setup = program.Clock()
    V, C, classes = layout.stream_shapes(model)
    T = int(model["gcn_frames"])
    clips = int(tr["clips_per_batch"])
    rows = clips * int(tr["persons"])
    nb = int(tr["batches"])
    cfg = program.model_config(conf)

    with setup.phase("weights+plans"):
        params2 = reference_ck.make_stream_params(model, seed)
        plans = program.build_plans(cfg, params2, conf)
    with setup.phase("inputs"):
        gen = jax.jit(lambda k: traffic.clip_batch(k, rows, T, V, C))
        keys = jax.random.split(jax.random.PRNGKey(seed % 2 ** 32), nb)
        batches = [jax.block_until_ready(gen(k)) for k in keys]
    with setup.phase("compile"):
        step = program.clip_step(cfg, plans, batches[0])
    families("clip", program.kernel_counts(step), FAMILIES, log)
    with setup.phase("warm"):
        for b in batches:
            np.asarray(step(plans, b))

    settle()
    tracer = Tracer(trace_dir, clock) if traced else None
    answers: List = []          # (batch index, t_start, t_done, logits)
    CompileCounter.install()
    compiles = CompileCounter.n
    ws = clock()
    we = ws + seconds
    t_len = min(seconds, float(tr.get("trace_s", seconds)))
    if tracer:
        tracer.on()
    i = 0
    with GcWatch() as gcw:
        while True:
            if tracer and tracer.t_off is None and clock() >= ws + t_len:
                tracer.off()
            t_s = clock()
            with span("bench.prepare"):
                xb = batches[i % nb]
            with span("bench.step"):
                y = step(plans, xb)
            with span("bench.readback"):
                yh = np.asarray(y)
            t_d = clock()
            if t_d > we:
                break
            answers.append((i % nb, t_s, t_d, yh))
            i += 1
    if tracer and tracer.t_off is None:
        tracer.off()
    setup_s = ws - t_start
    compiles = CompileCounter.n - compiles
    mem = memory_peak(jax.local_devices())
    done_clips = len(answers) * clips
    log_setup(setup, setup_s, log)
    log(f"window: {seconds} s, {len(answers)} steps of {clips} clips "
        f"({rows} rows), {done_clips} clips answered")
    log(f"peak HBM {mem} bytes; traces+compiles in the window {compiles}")
    log(gcw.line())

    ctx = None
    if tracer:
        sel = [a for a in answers
               if a[1] >= tracer.t_on and a[2] <= tracer.t_off]
        ctx = trace_context(trace.load(trace_dir), len(sel), model,
                            rows * len(sel), jax.devices()[0].device_kind)

    # free the program's state before the reference runs
    del step, plans
    which = np.array([a[0] for a in answers], np.int64)
    got = (np.stack([a[3] for a in answers]) if answers
           else np.zeros((0, rows, classes), np.float32))

    def reference_answers(num):
        """The reference's logits for every answer of the window."""
        ref = jax.jit(lambda p, x: reference_ck.clip_logits(p, x, model,
                                                            quant, num))
        want = np.stack([np.asarray(ref(params2, b)) for b in batches])
        return want[which]

    return {"e2e": {"setup_s": setup_s,
                    "clips_per_s": done_clips / seconds},
            "ctx": ctx, "attempted": done_clips, "failed": 0, "mem": mem,
            "got": got, "reference": reference_answers, "checks": {}}
