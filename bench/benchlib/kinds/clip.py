"""Closed-loop clip traffic (``"kind": "clip"``): one caller runs batches
of ``clips_per_batch`` clips of ``persons`` bodies each (one row per body)
back to back through the compiled two-stream clip step.  ``batches``
distinct batches are made on the device from the seed and cycled;
``trace_s`` is the traced part of the window.  Every answer of the window
is compared.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from benchlib import layout, program, reference, trace, traffic, work
from benchlib.cells import (CompileCounter, GcWatch, Tracer, families,
                            log_setup, memory_peak, settle, span,
                            trace_context)

FAMILIES = ("graph_sconv", "cavity_tconv", "rfc_encode", "rfc_decode")


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
        params2 = reference.make_stream_params(model, seed)
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
        counters = {"steps": len(sel),
                    "model_ops": 2 * rows * len(sel)
                    * work.model_ops_per_row(model)}
        red = trace.load(trace_dir)
        ctx = trace_context(red, counters, model, 2, rows * len(sel),
                            len(sel), jax.devices()[0].device_kind)

    # free the program's state before the reference runs
    del step, plans
    which = np.array([a[0] for a in answers], np.int64)
    got = (np.stack([a[3] for a in answers]) if answers
           else np.zeros((0, rows, classes), np.float32))

    def reference_answers(num):
        """The reference's logits for every answer of the window."""
        ref = jax.jit(lambda p, x: reference.clip_logits(p, x, model, quant,
                                                         num))
        want = np.stack([np.asarray(ref(params2, b)) for b in batches])
        return want[which]

    return {"e2e": {"setup_s": setup_s,
                    "clips_per_s": done_clips / seconds},
            "ctx": ctx, "attempted": done_clips, "failed": 0, "mem": mem,
            "got": got, "reference": reference_answers, "checks": {}}
