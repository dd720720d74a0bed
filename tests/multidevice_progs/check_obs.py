"""Subprocess: repro.obs end to end on 8 host devices.

Three contracts (the PR-9 acceptance criteria):

1. **Bit-identity** — an ``observe=True`` engine decodes the exact same
   tokens and final-step logits as an ``observe=False`` engine (spans and
   refit probes never touch the numerics); checked first, while the
   process-wide obs layer has never been enabled, so the off-engine is
   genuinely uninstrumented.
2. **Serve telemetry + online refit** — a skewed-traffic adaptive decode
   under ``observe=True`` produces (a) exactly one ``serve/replan``
   instant inside the exported Perfetto trace, (b) per-step
   ``serve/decode_step`` spans, and (c) non-empty ``refit_events`` whose
   fitted ``MachineParams`` landed on both ``engine.machine_params`` and
   the adaptive planner — the ROADMAP online-calibration loop, fed by
   production-step pure-exchange samples through the span bridge.
3. **AMG span tree** — hierarchy setup + solve emits the expected nested
   span structure (``amg/setup`` > ``amg/build_level`` per level,
   ``amg/solve`` > ``amg/place``, then ``amg/vcycle_iter`` per iteration
   split into ``amg/dispatch`` and ``amg/sync``, then ``amg/unpack``), and
   ``measure_exchange_seconds`` bridges one pure sample per level into
   the attached tracer without an explicit tracer argument.
"""
import json
import os
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)   # f64 AMG exchange timing

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.obs import default_obs


def make_engine(observe: bool, adaptive: bool, refit_every: int = 8):
    from repro.configs import reduced
    from repro.models import Model
    from repro.serve import ServeEngine

    cfg0 = reduced("mixtral-8x7b")
    cfg = cfg0.__class__(**{**cfg0.__dict__, "dtype": jnp.float32})
    mesh = jax.make_mesh((1, jax.device_count()), ("data", "model"))
    model = Model(cfg, mesh=mesh, moe_mode="auto", remat=False,
                  moe_cap_factor=8.0)
    params = model.init_params(seed=0)
    # The drift reference spans the planner's whole 8-observation window:
    # a decode step routes only 8 (token, expert) pairs over 4 experts, and
    # a 2-step reference of 16 pairs lets steady routing noise alone cross
    # the 0.3 threshold before the router is zeroed.
    return ServeEngine(model, params, batch_slots=2, max_len=96,
                       adaptive=adaptive, drift_threshold=0.3,
                       drift_warmup=8, observe=observe,
                       refit_every=refit_every), cfg


def submit_and_run(eng, cfg, n_steps):
    from repro.serve import Request

    rng = np.random.default_rng(1)
    for rid in range(2):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32),
            max_new_tokens=n_steps + 4,
        ))
    for _ in range(n_steps):
        eng.step()
    logits = eng._decode(
        eng.params, {"tokens": jnp.asarray(eng._next_tok)},
        eng.caches, jnp.asarray(eng.cur_len, jnp.int32),
    )[0]
    toks = [list(s.generated) for s in eng.slots if s is not None]
    return toks, np.asarray(logits)


def check_bit_identity():
    obs = default_obs()
    assert not obs.enabled, "must run before any obs-enabling check"
    toks_off, logits_off = submit_and_run(*make_engine(False, False), 12)

    # observe=True enables the process-wide layer; refit_every=4 forces
    # exchange probes + refits DURING the compared decode
    eng_on, cfg = make_engine(True, False, refit_every=4)
    toks_on, logits_on = submit_and_run(eng_on, cfg, 12)
    assert obs.enabled

    assert toks_on == toks_off, (toks_on, toks_off)
    assert np.array_equal(logits_on, logits_off), "logits must be bit-equal"
    n_steps = int(obs.counter("serve/steps", "").value())
    assert n_steps >= 12, n_steps
    print(f"bit-identity OK: {len(toks_on)} sequences, "
          f"{n_steps} instrumented steps, "
          f"{len(eng_on.refit_events)} refits during the compared decode")


def check_serve_observe():
    obs = default_obs()
    obs.reset()
    eng, cfg = make_engine(True, True, refit_every=8)
    from repro.serve import Request

    rng = np.random.default_rng(1)
    eng.submit(Request(
        rid=0,
        prompt=rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32),
        max_new_tokens=60,
    ))
    eng.step()
    for _ in range(12):                       # steady reference window
        eng.step()
    # zero router ties every logit -> top-k sends everything to experts
    # {0..k-1}: maximal histogram drift, exactly one re-selection
    eng.params["blocks"]["moe"]["router"] = jnp.zeros_like(
        eng.params["blocks"]["moe"]["router"]
    )
    for _ in range(20):
        eng.step()
        if eng.replan_events:
            break
    for _ in range(8):
        eng.step()

    assert len(eng.replan_events) == 1, eng.replan_events
    assert eng.refit_events, "periodic refit must have fired"
    assert eng.machine_params is not None
    assert eng.machine_params.name == "online-refit"
    # the fitted params drive subsequent adaptive re-selections
    assert eng.planner.params is eng.machine_params
    for ev in eng.refit_events:
        print(f"  {ev}")
    assert obs.tracer is not None and len(obs.tracer.samples) >= len(
        eng.refit_events), "each refit bridges >=1 pure probe sample"

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "serve_trace.json")
        obs.export_perfetto(path)
        doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert doc["otherData"]["schema_version"] == 1
    decode_spans = [e for e in evs
                    if e["ph"] == "X" and e["name"] == "serve/decode_step"]
    assert len(decode_spans) >= 20
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in decode_spans)
    replans = [e for e in evs
               if e["ph"] == "i" and e["name"] == "serve/replan"]
    assert len(replans) == 1
    assert replans[0]["args"]["drift"] >= 0.3
    refits = [e for e in evs
              if e["ph"] == "i" and e["name"] == "serve/refit"]
    assert len(refits) == len(eng.refit_events)
    assert any(e["ph"] == "C" for e in evs), "counter tracks sampled"
    print(f"serve observe OK: {len(decode_spans)} decode-step spans, "
          f"1 replan instant, {len(refits)} refit instants in Perfetto doc")


def check_amg_span_tree():
    from repro.amg.distributed import DistributedHierarchy
    from repro.amg.hierarchy import build_hierarchy
    from repro.profile.trace import TraceRecorder
    from repro.sparse.csr import CSR

    def poisson2d(nx):
        n = nx * nx
        rows, cols, vals = [], [], []
        for i in range(nx):
            for j in range(nx):
                k = i * nx + j
                rows.append(k); cols.append(k); vals.append(4.0)
                for di, dj in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                    ii, jj = i + di, j + dj
                    if 0 <= ii < nx and 0 <= jj < nx:
                        rows.append(k); cols.append(ii * nx + jj)
                        vals.append(-1.0)
        return CSR.from_coo(np.array(rows), np.array(cols),
                            np.array(vals), (n, n))

    obs = default_obs()
    obs.reset()
    tracer = TraceRecorder()
    obs.enable(tracer=tracer)

    A = poisson2d(24)
    h = build_hierarchy(A)
    mesh = Mesh(np.array(jax.devices()[:8]), ("proc",))
    dh = DistributedHierarchy.setup(h, mesh, "proc")
    b = np.random.default_rng(0).normal(size=A.nrows)
    _, hist = dh.solve(b, tol=0.0, max_iters=5)

    spans = obs.spans.events(kind="span")
    by_name = {}
    for e in spans:
        by_name.setdefault(e.name, []).append(e)
    assert "amg/setup" in by_name and by_name["amg/setup"][0].depth == 0
    n_levels = len(dh.levels)
    assert len(by_name["amg/build_level"]) == n_levels
    assert all(e.depth == 1 for e in by_name["amg/build_level"])
    # build-level spans carry the per-level selection verdicts
    for e in by_name["amg/build_level"]:
        assert {"level", "strategy", "layout", "ghost", "overlap"} \
            <= set(e.attrs)
    (solve,) = by_name["amg/solve"]
    assert solve.depth == 0 and solve.attrs["iters"] == len(hist)
    iters = by_name["amg/vcycle_iter"]
    assert len(iters) == len(hist) == 5
    assert all(e.depth == 1 for e in iters)
    # each iteration is the step's dispatch, then the sync that waits on it
    for it, d, s in zip(iters, by_name["amg/dispatch"], by_name["amg/sync"]):
        assert d.depth == s.depth == 2
        assert it.t0 <= d.t0 <= d.t1 <= s.t0 <= s.t1 <= it.t1
    assert len(by_name["amg/dispatch"]) == len(by_name["amg/sync"]) == 5
    (place,) = by_name["amg/place"]
    (unpack,) = by_name["amg/unpack"]
    assert place.depth == unpack.depth == 1
    assert place.t1 <= iters[0].t0 and iters[-1].t1 <= unpack.t0

    # no explicit tracer argument: the span bridge carries the samples
    # (one per level that actually exchanges — ghost-free levels skip)
    n_ex = sum(1 for lv in dh.levels if lv.A.ell.ghost_pad)
    assert n_ex > 0
    n0 = len(tracer.samples)
    secs = dh.measure_exchange_seconds()
    assert len(secs) == n_levels
    bridged = tracer.samples[n0:]
    assert len(bridged) == n_ex
    assert all(s.pure_exchange for s in bridged)
    names_now = {e.name for e in obs.spans.events(kind="span")}
    assert "amg/measure_exchange" in names_now
    print(f"amg span tree OK: {n_levels} levels, {len(hist)} V-cycle "
          f"iterations, {len(bridged)} bridged exchange samples")
    print(obs.span_tree().splitlines()[0])


def main():
    check_bit_identity()       # must run first: needs obs never-enabled
    check_serve_observe()
    check_amg_span_tree()
    print("ALL_OK")


if __name__ == "__main__":
    main()
