"""One run of one cell: set-up, the measured window, the check, the result.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and the chips; the cell's own file
``bench/workloads/<cell>.json`` holds the limits of its check.  Every metric
of ``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py``.  So a new
configuration, traffic mix, cell or metric is a new file and an entry, and
no file here changes.

The system under test is the program's sharded LAQ step
(``repro.launch.train.make_train_step``), compiled as
``jax.jit(step, donate_argnums=0)`` and fed one batch per round.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import correct
import peaks
import reference
import traffic

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def checked_rounds(mix: dict) -> int:
    """The first rounds, run in set-up, that the reference follows (three,
    or fewer where the mix says so to keep the reference shorter than the
    window)."""
    return mix.get("checked_rounds", 3)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def spec() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, traffic mix and limits."""
    bench = bench or spec()
    hits = [w for w in bench["workloads"] if w["name"] == name]
    if len(hits) != 1:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    w = hits[0]
    return dict(name=name, chips=w["chips"],
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                mix=traffic.load(w["traffic"]),
                limits=load_json(BENCH / "workloads" / f"{name}.json")["limits"])


def metrics_of(name: str, trace: bool, bench: dict | None = None) -> list:
    """The names of the metrics a run of cell ``name`` reports: the
    end-to-end ones, or with a trace the per-layer ones."""
    bench = bench or spec()
    group = bench["per_layer" if trace else "end_to_end"]
    return [m["name"] for m in group
            if "workloads" not in m or name in m["workloads"]]


def metric_module(name: str):
    """The module ``bench/metrics/<name>.py`` (names hold dots and dashes,
    so it is loaded by path)."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return metric_module(name).read


def check_chips(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")


def _program():
    """The program's modules (``src/`` of the checkout)."""
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core.criterion import CriterionConfig
    from repro.core.strategy import StrategyConfig, init_comm_state
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import device_mesh
    from repro.launch.train import (TrainState, make_train_step,
                                    train_state_specs)
    from repro.models.config import ModelConfig
    from repro.optim import adamw
    return dict(CriterionConfig=CriterionConfig, StrategyConfig=StrategyConfig,
                init_comm_state=init_comm_state,
                use_compile_cache=use_compile_cache, device_mesh=device_mesh,
                TrainState=TrainState, make_train_step=make_train_step,
                train_state_specs=train_state_specs, ModelConfig=ModelConfig,
                adamw=adamw)


def model_config(prog, config: dict):
    """The program's ModelConfig: the configuration's keys that name one of
    its fields."""
    import dataclasses
    from models.common import DTYPES
    fields = {f.name for f in dataclasses.fields(prog["ModelConfig"])}
    kw = {k: v for k, v in config.items() if k in fields}
    for k in ("param_dtype", "compute_dtype"):
        kw[k] = DTYPES[kw[k]]
    return prog["ModelConfig"](**kw)


def build(prog, config: dict, mix: dict, n_workers: int):
    """The program's step, optimizer, strategy and mesh for a cell."""
    s = mix["strategy"]
    strategy = prog["StrategyConfig"](
        kind=s["kind"], bits=s["bits"], per_leaf_radius=s["per_leaf_radius"],
        wire_backend=s["wire_backend"],
        first_round_upload=s.get("first_round_upload", True),
        criterion=prog["CriterionConfig"](**mix["criterion"]))
    o = mix["optimizer"]
    opt = prog["adamw"](b1=o["b1"], b2=o["b2"], eps=o["eps"],
                        weight_decay=o["weight_decay"])
    mesh = prog["device_mesh"](n_devices=n_workers)
    cfg = model_config(prog, config)
    step = prog["make_train_step"](cfg, mesh, strategy, opt, lr=mix["lr"],
                                   worker_axes=("data",), wire=mix["wire"],
                                   microbatch=mix["microbatches"])
    return dict(cfg=cfg, strategy=strategy, opt=opt, mesh=mesh, step=step)


def initial_state(prog, built, config: dict, key, n_workers: int):
    """The program's TrainState from the benchmark's weights, made on the
    device in one jitted call, in the shardings the program asks for."""
    specs = prog["train_state_specs"](built["cfg"], built["mesh"],
                                      built["strategy"], built["opt"],
                                      ("data",))

    def make(key):
        p = reference.weights(key, config)
        return prog["TrainState"](
            p, built["opt"].init(p),
            prog["init_comm_state"](p, n_workers, built["strategy"]),
            jnp.zeros((), jnp.int32))

    want = jax.tree.map(lambda s: (s.shape, s.dtype), specs)
    have = jax.tree.map(lambda s: (s.shape, s.dtype), jax.eval_shape(make, key))
    if jax.tree.structure(want) != jax.tree.structure(have) or want != have:
        raise ValueError("the benchmark's weights do not have the layout of "
                         "the program's parameters")
    shardings = jax.tree.map(lambda s: s.sharding, specs)
    return jax.jit(make, out_shardings=shardings)(key)


def make_pool(built, mix: dict, vocab: int, key, n_workers: int):
    """Every round's batch, made on the device from the seed in one call."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mix["pool_rounds"]

    def make(k):
        streams = traffic.round_streams(k, range(n), mix, vocab, n_workers)
        return [traffic.split(streams[i]) for i in range(n)]

    return jax.jit(make, out_shardings=NamedSharding(
        built["mesh"], P("data", None)))(key)


def program_readings(compiled, state, pool, config: dict, key, mix: dict,
                     rounds: int):
    """Drive the compiled step through the rounds the reference follows and
    read what the check compares.  Returns ``(state, readings)``."""
    b1, bits = mix["optimizer"]["b1"], mix["strategy"]["bits"]
    agg_norms = jax.jit(lambda s: reference.leaf_norms(
        jax.tree.map(lambda m: m / (1.0 - b1), s.opt_state.mu)))
    # one leaf at a time, so the codes add little to the device's peak
    codes = jax.jit(lambda q: reference.codes(q[0], bits)[0])
    change_norms = jax.jit(lambda s, k: reference.leaf_norms(jax.tree.map(
        lambda w, p: w - p.astype(jnp.float32), s.opt_state.master,
        reference.weights(k, config))))
    out = dict(losses=[], uploads=[], bits=[])
    for r in range(rounds):
        state, m = compiled(state, pool[r])
        m = jax.device_get(m)
        out["losses"].append(float(m.loss))
        out["uploads"].append(int(m.uploads))
        out["bits"].append(float(m.bits))
        if r == 0:
            out["agg_norms"] = np.asarray(agg_norms(state))
            out["codes"] = [np.asarray(codes(q))
                            for q in jax.tree.leaves(state.comm.qhat)]
    out["change_norms"] = np.asarray(change_norms(state, key))
    return state, out


def window(compiled, state, pool, first: int, seconds: float):
    """From batch ``first`` on, dispatch rounds back to back for
    ``seconds``, with Python's collector off; read each round's metrics on
    the host one round late.  Returns the state, the rounds completed, the
    window's seconds and the per-round metrics."""
    from jax.profiler import TraceAnnotation
    nxt, rounds, pending, per_round = first, 0, None, []
    gc.disable()
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.batch"):
                if nxt >= len(pool):
                    raise RuntimeError(f"the pool of {len(pool)} batches ran "
                                       "out: raise pool_rounds")
                b = pool[nxt]
                nxt += 1
            with TraceAnnotation("bench.dispatch"):
                state, m = compiled(state, b)
            rounds += 1
            if pending is not None:
                with TraceAnnotation("bench.readback"):
                    per_round.append(jax.device_get(pending))
            pending = m
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.drain"):
            jax.block_until_ready(state)
            per_round.append(jax.device_get(pending))
        t1 = time.perf_counter()
    gc.enable()
    return state, rounds, t1 - t0, per_round


def memory_stats(n_devices: int):
    return [d.memory_stats() or {} for d in jax.devices()[:n_devices]]


def peak_bytes(n_devices: int):
    """The fullest chip's peak: its arrays plus the space the runtime
    reserves for the programs' temporaries (not in ``peak_bytes_in_use``)."""
    vals = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
            for s in memory_stats(n_devices) if "peak_bytes_in_use" in s]
    return max(vals) if vals else None


def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        the_cell: dict | None = None, metric_names=None, require_tpu=True,
        fault=None, log=print):
    """One run of cell ``name``; returns the result line's object.

    ``the_cell``, ``metric_names`` and ``require_tpu=False`` let the tests
    drive a small cell on the CPU; ``fault(step) -> step`` breaks the timed
    path underneath, for the tests that see ``correct`` come out false.
    """
    c = the_cell or cell(name)
    if require_tpu:
        check_chips(c["chips"])
    names = metric_names if metric_names is not None else metrics_of(name, trace)
    config, mix = c["config"], c["mix"]
    W = c["chips"]
    prog = _program()
    prog["use_compile_cache"]()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    key = traffic.seed_key(seed)
    marks = [("start-up", time.perf_counter())]
    built = build(prog, config, mix, W)
    step = built["step"] if fault is None else fault(built["step"])
    state = jax.block_until_ready(initial_state(prog, built, config, key, W))
    marks.append(("state", time.perf_counter()))
    pool = jax.block_until_ready(make_pool(built, mix, config["vocab"], key,
                                           W))
    marks.append(("pool", time.perf_counter()))
    compiled = jax.jit(step, donate_argnums=0).lower(state, pool[0]).compile()
    marks.append(("compile", time.perf_counter()))
    log(f"memory before the step: {memory_stats(W)}")
    ma = compiled.memory_analysis()
    if ma is not None:
        log("the step's memory_analysis: " + ", ".join(
            f"{k} {getattr(ma, k)}" for k in dir(ma) if k.endswith("_in_bytes")))
    n_checked = checked_rounds(mix)
    state, prog_read = program_readings(compiled, state, pool, config, key,
                                        mix, n_checked)
    jax.block_until_ready(state)
    # set-up leaves a large heap behind; a full collection of it inside the
    # window would stall the dispatch loop, so collect it now and keep what
    # survives out of later collections
    gc.collect()
    gc.freeze()
    marks.append(("checked rounds", time.perf_counter()))
    setup_s = marks[-1][1] - t_start
    prev = t_start
    parts = []
    for what, t in marks:
        parts.append(f"{what} {t - prev:.3f} s")
        prev = t
    log("set-up: " + ", ".join(parts))

    tmp = tempfile.TemporaryDirectory() if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp.name)
        try:
            state, rounds, window_s, per_round = window(
                compiled, state, pool, n_checked, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        peak = peak_bytes(W)
        log(f"memory after the window: {memory_stats(W)}")
        del state, compiled, pool
        gc.collect()

        for i, m in enumerate(per_round):
            log(f"round {n_checked + i} loss {float(m.loss)!r} uploads "
                f"{int(m.uploads)} skips {W - int(m.uploads)} "
                f"wire_bits {float(m.bits)!r}")
        failed = sum(not np.isfinite(float(m.loss)) for m in per_round)

        t_ref = time.perf_counter()
        ref = reference.run(seed, config, mix, W)
        log(f"reference: {time.perf_counter() - t_ref:.3f} s")
        numbers = correct.readings(prog_read, ref)
        log(f"program losses {prog_read['losses']} uploads "
            f"{prog_read['uploads']}; reference losses {ref['losses']} "
            f"uploads {ref['uploads']}")
        log(f"worst leaves: grad {numbers['grad_gap_leaf']} update "
            f"{numbers['update_gap_leaf']} codes "
            f"{numbers['code_mismatch_leaf']}; left out {numbers['left_out']}, "
            f"{numbers['code_ties']} codes at a tie")
        ok, checks = correct.judge(numbers, c["limits"])
        log("not compared: " + " ".join(
            f"{k} {numbers[k]!r}" for k in correct.NUMBERS if k not in checks))

        dev = jax.devices()[0]
        ctx = dict(setup_s=setup_s, window_s=window_s, rounds=rounds,
                   tokens=rounds * traffic.tokens_per_round(mix, W),
                   peak_bytes=peak, config=config, mix=mix, chips=W,
                   device_kind=dev.device_kind,
                   peaks=None, trace=None, span=None)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": W, "memory_peak_bytes": peak}
        result = {"correct": ok, "attempted": rounds, "failed": failed}
        if trace:
            import traces as trace_mod
            tr = trace_mod.load(trace_mod.find_xplane(tmp.name))
            t0, t1 = tr.window()
            planes = sorted(tr.devices)[:W]
            busy = [trace_mod.busy_ns(trace_mod.clip(tr.devices[p], t0, t1))
                    for p in planes]
            device["busy_s"] = float(np.mean(busy)) * 1e-9
            device["window_s"] = (t1 - t0) * 1e-9
            ctx.update(trace=tr, span=(t0, t1), planes=planes)
            result["breakdown"] = trace_mod.breakdown(tr, t0, t1)
    finally:
        if tmp is not None:
            tmp.cleanup()

    if ctx["device_kind"] in peaks.PEAKS:
        ctx["peaks"] = peaks.PEAKS[ctx["device_kind"]]
    elif require_tpu:
        raise ValueError(f"no peaks for device kind {ctx['device_kind']!r}")
    unit_of = units() if metric_names is None else {}
    metrics = {}
    for n in names:
        v = metric_reader(n)(ctx)
        if v is not None:
            metrics[n] = {"value": float(v), "unit": unit_of.get(n)}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return result


def units() -> dict:
    """Each metric's unit, from ``BENCHMARK.json``."""
    b = spec()
    return {m["name"]: m["unit"] for m in b["end_to_end"] + b["per_layer"]}
