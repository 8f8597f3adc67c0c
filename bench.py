"""North-star benchmark: events/sec/chip on a 1M-key tumbling-window sum.

Subject: flink_tpu's keyed windowed aggregation (columnar source -> keyBy ->
5s event-time tumbling window -> sum -> counting sink) through the real
executor on the TPU (--cpu for the virtual CPU mesh). Without --cpu a run
fails unless JAX came up on an accelerator: no mode falls back to the CPU,
and every failure exits non-zero.

Baseline: the reference's HeapKeyedStateBackend hot path re-implemented
faithfully in-process (per-record: hash -> dict probe -> reduce -> put;
watermark advance -> per-key timer drain; SURVEY §3.2/3.3). The reference
itself (JVM Flink 1.2) cannot run in this image, so the baseline is the same
scalar algorithm in optimized Python; see BASELINE.md.

Prints ONE JSON line:
  {"metric": ..., "value": events_per_sec, "unit": "events/s", "vs_baseline": x}
"""

import argparse
import json
import os
import platform
import subprocess
import sys
import time

import numpy as np

N_KEYS = 1_000_000
WINDOW_MS = 5_000
EVENTS_PER_MS = 2_000          # event-time rate: 10M events per 5s window
BATCH = 262_144
FIRES_PER_STEP = 2
MAX_INFLIGHT = None            # None = runtime default
# candidate micro-batch sizes for the on-TPU calibration sweep: a larger
# batch amortizes the fixed per-step dispatch cost; the sweep measures
# instead of guessing
SWEEP = (262_144, 524_288, 1_048_576)
PIN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE_PIN.json")


def gen_batch(offset, n):
    idx = np.arange(offset, offset + n, dtype=np.int64)
    keys = (idx * 2862933555777941757) % N_KEYS
    ts = idx // EVENTS_PER_MS
    return keys, ts, np.ones(n, np.float32)


# ---------------------------------------------------------------- baseline
def run_baseline(total_events: int):
    """Scalar per-record loop with dict-probe state + per-key fire drain.

    Returns (events/s, fire-latency samples [(n_windows, ms), ...]) where
    latency is watermark-crossing -> emission, chunked every 8192 windows
    of the sequential per-key timer drain (ref WindowOperator.onEventTime:
    one callback per key on the task thread)."""
    state = {}          # (key, pane) -> acc   (the StateTable analog)
    fired = []
    lat = []            # (n_windows, ms) weighted fire-latency samples
    wm_pane = -1
    done = 0
    t0 = time.perf_counter()
    off = 0
    while done < total_events:
        keys, ts, vals = gen_batch(off, min(BATCH, total_events - done))
        off += len(keys)
        kl, tl = keys.tolist(), ts.tolist()
        for i in range(len(kl)):
            k = kl[i]
            pane = tl[i] // WINDOW_MS
            sk = (k, pane)
            cur = state.get(sk)          # HashMap probe
            state[sk] = 1.0 if cur is None else cur + 1.0  # reduce + put
        done += len(kl)
        # watermark advance: fire panes older than max ts (timer drain)
        new_wm_pane = tl[-1] // WINDOW_MS - 1
        if new_wm_pane > wm_pane:
            t_cross = time.perf_counter()
            chunk = 0
            for p in range(wm_pane + 1, new_wm_pane + 1):
                drain = [sk for sk in state if sk[1] == p]
                for sk in drain:
                    fired.append((sk[0], state.pop(sk)))
                    chunk += 1
                    if chunk >= 8192:
                        lat.append(
                            (chunk, (time.perf_counter() - t_cross) * 1e3)
                        )
                        chunk = 0
            if chunk:
                lat.append((chunk, (time.perf_counter() - t_cross) * 1e3))
            wm_pane = new_wm_pane
    # end-of-stream drain of still-open panes (MAX-watermark analog)
    t_cross = time.perf_counter()
    n_left = len(state)
    for sk in list(state):
        fired.append((sk[0], state.pop(sk)))
    if n_left:
        lat.append((n_left, (time.perf_counter() - t_cross) * 1e3))
    dt = time.perf_counter() - t0
    return done / dt, lat


def _weighted_pct(samples, q):
    """Percentile over windows from weighted (n, ms) samples (shared
    implementation with JobMetrics.fire_latency_pct)."""
    from flink_tpu.metrics.latency import weighted_percentile

    return weighted_percentile(samples, q)


# ---------------------------------------------------------------- pinning
def pin_baseline(n_runs: int, events: int):
    """Measure the baseline n_runs times on a quiet host and pin the BEST
    run (throughput and its fire latencies) to BASELINE_PIN.json.

    VERDICT r3 item 3: the co-measured baseline swings ~7x with host load,
    so ratios against it are not defensible. The pinned number is the
    baseline's best case — every future ratio quoted against it is
    conservative. Protocol recorded in the artifact itself."""
    runs = []
    for i in range(n_runs):
        eps, lat = run_baseline(events)
        runs.append({
            "events_per_s": round(eps),
            "fire_p50_ms": round(_weighted_pct(lat, 50) or 0, 2),
            "fire_p99_ms": round(_weighted_pct(lat, 99) or 0, 2),
        })
        print(f"pin run {i + 1}/{n_runs}: {eps:,.0f} events/s",
              file=sys.stderr)
    best = max(runs, key=lambda r: r["events_per_s"])
    pin = {
        "baseline_events_per_s": best["events_per_s"],
        "baseline_fire_p50_ms": best["fire_p50_ms"],
        "baseline_fire_p99_ms": best["fire_p99_ms"],
        "protocol": {
            "runs": n_runs, "pick": "best-of-N throughput",
            "events": events, "batch": BATCH,
            "host": platform.node(), "python": platform.python_version(),
            "pinned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
        "all_runs": runs,
    }
    with open(PIN_PATH, "w") as f:
        json.dump(pin, f, indent=1)
    print(json.dumps(pin["all_runs"]), file=sys.stderr)
    print(f"pinned best-of-{n_runs} -> {PIN_PATH}", file=sys.stderr)


def load_pin():
    if not os.path.exists(PIN_PATH):
        return None
    with open(PIN_PATH) as f:
        return json.load(f)


# ---------------------------------------------------------------- subject
def run_subject(total_events: int, warmup_events: int, batch: int = None) -> tuple:
    import jax

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    def gen(offset, n):
        keys, ts, vals = gen_batch(offset, n)
        return {"key": keys, "value": vals}, ts

    cfg = Configuration({
        "keys.reverse-map": False,
        # 2 fire lanes per drain step: each lane costs 3 full-capacity
        # pack scatters in the packed variant (the CountingSink rides
        # the ReducedFires drain, where lanes are nearly free), and a
        # tumbling boundary only ever has 1 due end
        "window.fires-per-step": FIRES_PER_STEP,
    })
    if MAX_INFLIGHT is not None:
        # tunable fire-wait vs throughput tradeoff: the p99 drain waits
        # behind up to this many queued update steps
        cfg.set("pipeline.max-inflight-steps", MAX_INFLIGHT)
    env = StreamExecutionEnvironment(cfg)
    env.set_parallelism(len(jax.devices()))
    env.set_max_parallelism(128)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    # capacity == keyspace: keys are ints in [0, N_KEYS), so the auto
    # state layout resolves to the DIRECT-INDEX backend (key == slot — no
    # probe gathers, no insert phase; wk.init_state layout="direct"),
    # the layout a user tuning this job would pick, like choosing the
    # heap vs RocksDB backend in the reference
    env.set_state_capacity(N_KEYS)
    env.batch_size = batch or BATCH

    sink = CountingSink()

    timings = {"t_first": None, "t_start": time.perf_counter()}

    class TimingSource(GeneratorSource):
        def poll(self, max_records):
            out = super().poll(max_records)
            if self.offset >= warmup_events and timings["t_first"] is None:
                timings["t_first"] = time.perf_counter()
            return out

    (
        env.add_source(TimingSource(gen, total=total_events))
        .key_by(lambda c: c["key"])
        .time_window(WINDOW_MS)
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )
    t0 = time.perf_counter()
    job = env.execute("bench-1m-key-window-sum")
    t1 = time.perf_counter()
    measured = total_events - warmup_events
    steady = measured / (t1 - timings["t_first"])
    assert sink.value_sum == total_events, (sink.value_sum, total_events)
    return steady, job, sink


def _cpu_child_env(n_devices: int) -> dict:
    """Environment for a child on an n-device virtual CPU mesh: the
    device count must be forced before JAX initializes, so a mode that
    needs its own count runs in a child. The child never needs the chip,
    and its code turns the compile cache off (``_CPU_CHILD``)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (
        f"{xla} --xla_force_host_platform_device_count={n_devices}".strip()
    )
    return env


# the persistent compile cache's executable (de)serialization segfaults
# under a forced virtual CPU mesh in this jaxlib (reproducible ~90%; clean
# 0/7 without it), so CPU children compile fresh
_CPU_CHILD = "import json, jax; jax.config.update('jax_enable_compilation_cache', False); "


def main():
    global BATCH, FIRES_PER_STEP, MAX_INFLIGHT

    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="CPU mesh instead of TPU")
    ap.add_argument("--events", type=int, default=30_000_000)
    ap.add_argument("--baseline-events", type=int, default=2_000_000)
    ap.add_argument("--batch", type=int, default=None,
                    help="micro-batch size (default BATCH)")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the batch-size calibration sweep")
    ap.add_argument("--inflight", type=int, default=None,
                    help="pipeline.max-inflight-steps (p99 vs throughput)")
    ap.add_argument("--fires", type=int, default=FIRES_PER_STEP,
                    help="window.fires-per-step")
    ap.add_argument("--pin-baseline", type=int, default=0, metavar="N",
                    help="measure the baseline N times on this (quiet) "
                         "host, write best-of-N to BASELINE_PIN.json, exit")
    ap.add_argument("--device-ceiling", action="store_true",
                    help="run ONLY the device_update_ceiling microbench "
                         "(pre-staged batch ring, no source): K-fusion x "
                         "duplicate-fraction grid + precombine on/off")
    ap.add_argument("--stages", action="store_true",
                    help="run ONLY the chained 2-stage drain vs "
                         "single-stage comparison at matched dims "
                         "(ISSUE 16): events/s + p99_fire_ms per "
                         "discipline")
    ap.add_argument("--resident", action="store_true",
                    help="run ONLY the resident_loop microbench: ring-"
                         "drain dispatches (one per 32 staged slots) vs "
                         "K=8 fused megasteps at matched dims, stamping "
                         "host dispatches per 1k events + events/s")
    ap.add_argument("--mttr", action="store_true",
                    help="run ONLY the mttr_recovery drill: detect-to-"
                         "first-fire of cold-remote vs local vs warm "
                         "recovery paths, per-phase breakdowns in the "
                         "detail JSON")
    ap.add_argument("--elastic", action="store_true",
                    help="run ONLY the elastic_recovery drill on an "
                         "8-device CPU mesh: kill 1 shard, measure "
                         "degraded-throughput fraction + rescale MTTR "
                         "+ the exactly-once oracle across the "
                         "lose-one -> scale-back cycle")
    ap.add_argument("--tiered", action="store_true",
                    help="run ONLY the tiered key-group state config "
                         "(ISSUE 18): Zipf cold-tail stream with >10x "
                         "more key-groups than the HBM-resident "
                         "budget, events/s as a fraction of the all-"
                         "resident baseline + p99_fire_ms + prefetch "
                         "hit/miss counts")
    ap.add_argument("--selftune", action="store_true",
                    help="run ONLY the self-tuning drill (ISSUE 19): "
                         "skew-shifting stream on a 4-device CPU mesh "
                         "whose hot key-groups migrate mid-run; the "
                         "controller's live rebalance must restore "
                         ">= 0.8x balanced throughput without restart "
                         "while the controller-off run stays degraded")
    ap.add_argument("--scaling", action="store_true",
                    help="run ONLY the chips-vs-events/s curve (ISSUE "
                         "13): the sharded resident drain at matched "
                         "dims on 1/2/4/8 virtual CPU devices, one "
                         "child process per chip count, stamping total "
                         "events/s + parallel efficiency per cell")
    args = ap.parse_args()
    if args.batch:
        BATCH = args.batch
    FIRES_PER_STEP = args.fires
    MAX_INFLIGHT = args.inflight

    if args.pin_baseline:
        pin_baseline(args.pin_baseline, args.baseline_events)
        return

    if args.cpu:
        # before JAX initializes: JAX reads the variable at start-up
        os.environ["JAX_PLATFORMS"] = "cpu"
    if not (args.elastic or args.selftune or (args.scaling and args.cpu)):
        # every other mode measures in THIS process: it owns the chip
        from flink_tpu.core.jaxenv import (
            device_stamp,
            require_accelerator,
            use_compile_cache,
        )

        use_compile_cache()
        if not args.cpu:
            require_accelerator()

    if args.device_ceiling:
        # device-ceiling mode: the pure on-device update+fire grid (the
        # compute ceiling VERDICT r5 flags), no source / no baseline run
        from bench_configs import (
            DEVICE_CEILING_BATCH,
            run_device_update_ceiling,
        )

        fused_best, split_best = run_device_update_ceiling(
            args.events, args.cpu
        )
        print(json.dumps({
            "metric": "device ceiling, best fused-fire cell vs best "
                      "split-dispatch (PR-5 path) cell, firing stream",
            "value": round(fused_best),
            "unit": "events/s",
            "vs_baseline": (
                round(fused_best / split_best, 2) if split_best else 0
            ),
            "criterion": ">= 1.15",
            "batch": DEVICE_CEILING_BATCH,
        }))
        return

    if args.resident:
        # resident-loop mode (ISSUE 12): ring-drain vs K=8 megastep
        # dispatch disciplines on the firing stream; the detail JSON with
        # the per-cell grid and the per-1k-events dispatch counts prints
        # from inside the config
        from bench_configs import DEVICE_CEILING_BATCH, run_resident_loop

        res_best, fused_best, res_p99, fused_p99 = run_resident_loop(
            args.events, args.cpu
        )
        print(json.dumps({
            "metric": "resident ring-drain best cell vs best K=8 "
                      "fused-megastep (PR-7 path) cell, firing stream",
            "value": round(res_best),
            "unit": "events/s",
            "p99_fire_ms": res_p99,
            "vs_baseline": (
                round(res_best / fused_best, 2) if fused_best else 0
            ),
            "criterion": ">= 1.15",
            "dispatch_drop": 4.0,
            "fused_p99_fire_ms": fused_p99,
            "batch": DEVICE_CEILING_BATCH,
        }))
        # round-20 rows: early-exit while drain vs the scan drain at
        # matched dims, and the per-host DCN-resident mode vs lockstep
        from bench_configs import run_dcn_resident, run_while_drain

        w_eps, s_eps, w_p99, s_p99 = run_while_drain(
            args.events, args.cpu
        )
        print(json.dumps({
            "metric": "early-exit while drain (max_slots=64) vs "
                      "count-gated scan drain (D=32), matched dims, "
                      "firing stream",
            "value": round(w_eps),
            "unit": "events/s",
            "p99_fire_ms": w_p99,
            "vs_baseline": round(w_eps / s_eps, 2) if s_eps else 0,
            "criterion": ">= 1.0 events/s AND >= 1.5x fewer "
                         "dispatches/1k-events (structural 2x)",
            "scan_events_per_s": round(s_eps),
            "scan_p99_fire_ms": s_p99,
            "batch": DEVICE_CEILING_BATCH,
        }))
        r_eps, l_eps, r_cyc, l_cyc = run_dcn_resident(
            args.events, args.cpu
        )
        print(json.dumps({
            "metric": "per-host DCN-resident drains vs single-step "
                      "lockstep rounds",
            "value": round(r_eps),
            "unit": "events/s",
            "vs_baseline": round(r_eps / l_eps, 2) if l_eps else 0,
            "criterion": ">= 1.3x (two-process); see detail.mode for "
                         "the measured topology",
            "cycles": r_cyc,
            "lockstep_cycles": l_cyc,
        }))
        return

    if args.stages:
        # chained-stages mode (ISSUE 16): 2-stage chained drain vs the
        # single-stage resident drain at matched dims; the acceptance
        # criterion is <15% throughput cost for the extra stage, with
        # fire-visibility p99 stamped beside events/s for both
        from bench_configs import DEVICE_CEILING_BATCH, run_chained_stages

        s_eps, c_eps, s_p99, c_p99 = run_chained_stages(
            args.events, args.cpu
        )
        print(json.dumps({
            "metric": "chained 2-stage keyed drain vs single-stage "
                      "resident drain, matched dims, firing stream",
            "value": round(c_eps),
            "unit": "events/s",
            "p99_fire_ms": c_p99,
            "vs_baseline": round(c_eps / s_eps, 2) if s_eps else 0,
            "criterion": ">= 0.85 (<15% throughput cost vs "
                         "single-stage)",
            "single_stage_events_per_s": round(s_eps),
            "single_stage_p99_fire_ms": s_p99,
            "batch": DEVICE_CEILING_BATCH,
        }))
        return

    if args.tiered:
        # tiered-state mode (ISSUE 18): cold-tail stream through the
        # full executor, tiered vs all-resident; the detail JSON with
        # both rows and the acceptance fraction prints from inside the
        # config
        from bench_configs import run_tiered

        t_eps, base_eps, t_p99, tiers = run_tiered(args.events, args.cpu)
        print(json.dumps({
            "metric": "tiered key-group state: Zipf cold-tail stream, "
                      ">10x more key-groups than the HBM-resident "
                      "budget, vs the all-resident baseline",
            "value": round(t_eps),
            "unit": "events/s",
            "p99_fire_ms": t_p99,
            "vs_baseline": round(t_eps / base_eps, 2) if base_eps else 0,
            "criterion": ">= 0.6",
            "all_resident_events_per_s": round(base_eps),
            **tiers,
        }))
        return

    if args.elastic:
        # elasticity drill (ISSUE 8): defined on the 8-device virtual
        # CPU mesh, so it runs in a CPU child, with one retry: the
        # virtual 8-device CPU mesh occasionally segfaults inside XLA
        # under heavy compile/dispatch concurrency (environment-level
        # flake)
        child_env = _cpu_child_env(8)
        code = (
            _CPU_CHILD +
            "from bench_configs import run_elastic_recovery; "
            f"frac, mttr, p99 = run_elastic_recovery({args.events}, True); "
            "print('ELASTIC_RESULT ' + json.dumps([frac, mttr, p99]))"
        )
        result, last_err = None, "no attempts ran"
        for attempt in range(2):
            try:
                r = subprocess.run(
                    [sys.executable, "-c", code], env=child_env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                    timeout=1200, capture_output=True, text=True,
                )
            except subprocess.TimeoutExpired:
                last_err = "drill child timed out (>1200s)"
                continue
            sys.stderr.write(r.stderr)
            for line in r.stdout.splitlines():
                if line.startswith("ELASTIC_RESULT "):
                    result = json.loads(line[len("ELASTIC_RESULT "):])
                else:
                    print(line)     # the drill's detail JSON passes up
            if result is not None:
                break
            last_err = (
                f"drill child rc={r.returncode}: "
                f"{(r.stderr or r.stdout).strip()[-300:]}"
            )
            print(f"elastic drill attempt {attempt + 1} failed; "
                  f"retrying: {last_err}", file=sys.stderr)
        if result is None:
            sys.exit(f"elastic drill failed twice: {last_err}")
        frac, mttr_ms, p99_ms = result
        print(json.dumps({
            "metric": "elastic recovery: degraded throughput fraction "
                      "after losing 1 of 8 shards",
            "value": round(frac, 3),
            "unit": "fraction of pre-fault throughput",
            "p99_fire_ms": p99_ms,
            "vs_baseline": round(frac / (7 / 8), 3),
            "criterion": ">= 0.6 * (7/8) = 0.525",
            "rescale_detect_to_first_fire_ms": mttr_ms,
        }))
        return

    if args.selftune:
        # self-tuning drill (ISSUE 19): defined on the 4-device virtual
        # CPU mesh, so it runs in a CPU child with one retry, like the
        # elastic drill
        child_env = _cpu_child_env(4)
        code = (
            _CPU_CHILD +
            "from bench_configs import run_selftune; "
            f"on, off, p99, ctl = run_selftune({args.events}, True); "
            "print('SELFTUNE_RESULT ' + json.dumps([on, off, p99, ctl]))"
        )
        result, last_err = None, "no attempts ran"
        for attempt in range(2):
            try:
                r = subprocess.run(
                    [sys.executable, "-c", code], env=child_env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                    timeout=1200, capture_output=True, text=True,
                )
            except subprocess.TimeoutExpired:
                last_err = "selftune child timed out (>1200s)"
                continue
            sys.stderr.write(r.stderr)
            for line in r.stdout.splitlines():
                if line.startswith("SELFTUNE_RESULT "):
                    result = json.loads(line[len("SELFTUNE_RESULT "):])
                else:
                    print(line)     # the drill's detail JSON passes up
            if result is not None:
                break
            last_err = (
                f"selftune child rc={r.returncode}: "
                f"{(r.stderr or r.stdout).strip()[-300:]}"
            )
            print(f"selftune drill attempt {attempt + 1} failed; "
                  f"retrying: {last_err}", file=sys.stderr)
        if result is None:
            sys.exit(f"selftune drill failed twice: {last_err}")
        ratio_on, ratio_off, p99_ms, ctl = result
        print(json.dumps({
            "metric": "self-tuning controller: skew-shifting stream, "
                      "hot key-groups migrate mid-run; live rebalance "
                      "tail throughput vs balanced baseline",
            "value": ratio_on,
            "unit": "fraction of balanced tail throughput",
            "p99_fire_ms": p99_ms,
            "vs_baseline": (
                round(ratio_on / ratio_off, 2) if ratio_off else 0
            ),
            "criterion": ">= 0.8 of balanced throughput without "
                         "restart; controller-off stays degraded",
            "controller_off_fraction": ratio_off,
            **ctl,
        }))
        return

    if args.scaling:
        # chips-vs-events/s curve (ISSUE 13). On the chip every cell runs
        # in THIS process over the first n devices, so one process holds
        # the chips. Under --cpu each cell needs its own forced virtual
        # device count: one CPU child per cell, one retry each.
        curve, p99s, errs = {}, {}, []
        if not args.cpu:
            from bench_configs import run_scaling_cell

            dev = device_stamp()
            for n_chips in (1, 2, 4, 8):
                if n_chips <= dev["count"]:
                    _n, eps, cell_p99 = run_scaling_cell(
                        args.events, n_devices=n_chips)
                    curve[str(n_chips)] = round(eps)
                    p99s[str(n_chips)] = cell_p99
        else:
            dev = {"platform": "cpu", "kind": "virtual", "count": 8}
            for n_chips in (1, 2, 4, 8):
                code = (
                    _CPU_CHILD +
                    "from bench_configs import run_scaling_cell; "
                    f"n, eps, p99 = run_scaling_cell({args.events}); "
                    "print('SCALING_RESULT ' + json.dumps([n, eps, p99]))"
                )
                cell = None
                for attempt in range(2):
                    try:
                        r = subprocess.run(
                            [sys.executable, "-c", code],
                            env=_cpu_child_env(n_chips),
                            cwd=os.path.dirname(os.path.abspath(__file__)),
                            timeout=900, capture_output=True, text=True,
                        )
                    except subprocess.TimeoutExpired:
                        errs.append(f"{n_chips}-chip cell timed out")
                        continue
                    sys.stderr.write(r.stderr)
                    for line in r.stdout.splitlines():
                        if line.startswith("SCALING_RESULT "):
                            cell = json.loads(line[len("SCALING_RESULT "):])
                    if cell is not None:
                        break
                    errs.append(
                        f"{n_chips}-chip cell rc={r.returncode}: "
                        f"{(r.stderr or r.stdout).strip()[-200:]}"
                    )
                if cell is None or cell[0] != n_chips:
                    sys.exit(f"{n_chips}-chip scaling cell failed: {errs}")
                curve[str(n_chips)] = round(cell[1])
                p99s[str(n_chips)] = cell[2]
        one = curve["1"]
        best = max(curve.values())
        best_chips = max(curve, key=curve.get)
        print(json.dumps({
            "metric": "multi-chip scaling: sharded resident drain, "
                      "total events/s at 1/2/4/8 devices",
            "value": best,
            "unit": "events/s",
            "p99_fire_ms": p99s.get(best_chips),
            "vs_baseline": round(best / one, 2),
            "events_per_s_by_chips": curve,
            "p99_fire_ms_by_chips": p99s,
            "parallel_efficiency": {
                c: round(v / (int(c) * one), 3)
                for c, v in curve.items()
            },
            "shared_cores": args.cpu,
            "device": dev,
            "errors": errs,
        }))
        return

    if args.mttr:
        # MTTR drill mode (ISSUE 6): the detail JSON line with per-phase
        # timings prints from inside the config; this summary line is
        # the acceptance number (cold-remote / warm >= 2)
        from bench_configs import run_mttr_recovery

        cold_ms, warm_ms = run_mttr_recovery(args.events, args.cpu)
        print(json.dumps({
            "metric": "MTTR detect-to-first-fire, cold-remote vs warm",
            "value": warm_ms,
            "unit": "ms",
            "vs_baseline": round(cold_ms / warm_ms, 2) if warm_ms else 0,
            "cold_remote_ms": cold_ms,
        }))
        return

    def fmt(ms):
        return f"{ms:.1f}ms" if ms is not None else "n/a"

    def rnd(ms):
        return round(ms, 2) if ms is not None else None

    baseline_eps, base_lat = run_baseline(args.baseline_events)
    base_p50 = _weighted_pct(base_lat, 50)
    base_p99 = _weighted_pct(base_lat, 99)
    print(
        f"baseline (scalar heap path): {baseline_eps:,.0f} events/s | "
        f"fire p50={fmt(base_p50)} p99={fmt(base_p99)}",
        file=sys.stderr,
    )

    # -- batch-size calibration sweep (TPU only; CPU smoke is compile-
    # dominated and would mis-calibrate): short steady-state run per
    # candidate, full run at the winner. Events scale with the batch so
    # every candidate measures the same ~18 steady steps.
    sweep_rows = {}
    if not args.cpu and args.batch is None and not args.no_sweep:
        for cand in SWEEP:
            # warmup in STEPS, not events: compile + any adaptive
            # tiering settle per step (~25 steps), so each candidate
            # must be measured in the same post-settle regime; a
            # failing candidate fails the run
            eps_c, job_c, _ = run_subject(43 * cand, 25 * cand, batch=cand)
            sweep_rows[cand] = round(eps_c)
            print(
                f"sweep batch={cand}: {eps_c:,.0f} events/s "
                f"(p99 fire {fmt(job_c.metrics.fire_latency_pct(99))})",
                file=sys.stderr,
            )
        BATCH = max(sweep_rows, key=sweep_rows.get)
        print(f"sweep winner: batch={BATCH}", file=sys.stderr)

    # warmup covers backend init + cold-start key inserts + the adaptive
    # switch to the lookup-only fast tier. The tier switch is STEP-count
    # driven (~25 steps: MON_EVERY x TIER_QUIET_CHECKS sampling), so the
    # warmup floor scales with the batch size the sweep picked;
    # steady-state throughput is what the metric claims
    warmup = min(max(args.events // 3, 25 * BATCH), args.events // 2)
    subject_eps, job, sink = run_subject(args.events, warmup)
    subj_p50 = job.metrics.fire_latency_pct(50)
    subj_p99 = job.metrics.fire_latency_pct(99)
    print(
        f"subject: {subject_eps:,.0f} events/s steady-state | fires={sink.count:,}"
        f" | steps={job.metrics.steps} | late={job.metrics.dropped_late}"
        f" | cap={job.metrics.dropped_capacity}"
        f" | fire p50={fmt(subj_p50)} p99={fmt(subj_p99)}",
        file=sys.stderr,
    )

    # ratio policy (VERDICT r3 item 3): quote against the PINNED quiet-host
    # best-of-N baseline when one exists — conservative and reproducible —
    # and carry the co-measured ratio alongside for context
    pin = load_pin()
    pinned_eps = pin["baseline_events_per_s"] if pin else None
    primary = pinned_eps or baseline_eps
    out = {
        "metric": "events/sec/chip, 1M-key 5s tumbling-window sum",
        "value": round(subject_eps),
        "unit": "events/s",
        "vs_baseline": round(subject_eps / primary, 2),
        "baseline_source": "pinned-best-of-N" if pin else "co-measured",
        "vs_baseline_comeasured": round(subject_eps / baseline_eps, 2),
        "p99_fire_ms": rnd(subj_p99),
        "p50_fire_ms": rnd(subj_p50),
        "baseline_p99_fire_ms": rnd(base_p99),
        "baseline_p50_fire_ms": rnd(base_p50),
        "batch": BATCH,
        "fires_per_step": FIRES_PER_STEP,
        "max_inflight": MAX_INFLIGHT,
        "device": device_stamp(),
    }
    if pin:
        out["baseline_pinned_events_per_s"] = pinned_eps
        out["baseline_pinned_p99_fire_ms"] = pin["baseline_fire_p99_ms"]
    if sweep_rows:
        out["sweep"] = {str(k): v for k, v in sweep_rows.items()}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
