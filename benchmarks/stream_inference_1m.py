"""BASELINE config 5 at the titular scale: 1,000,000 rows of
ResNet-50 inference through the columnar streaming path, measured end
to end on the real chip — no ``projected_`` anything.

Disk reality: 1M rows of 224x224x3 uint8 = 150.5 GB, which does not
fit this rig's free disk (~79 GB). The dataset is therefore a
``--dataset-rows`` Parquet file (default 400k rows = 60 GB, the
largest that fits with headroom) streamed in consecutive passes until
1M rows have gone disk -> decode -> host->device wire -> compiled
forward -> argmax readback. Every row of every pass does the full
traversal; per-pass rates are reported separately so any page-cache
effect on later passes is visible rather than hidden (the measured
bottleneck is the host->device wire, not disk — see the saturation
analysis in the output row).

Resumable: progress (total rows done) is checkpointed to a state file
after every drained batch; rerunning with the same --state resumes
mid-pass by skipping already-processed rows of the current pass.

Usage: python benchmarks/stream_inference_1m.py [--rows 1000000]
       [--dataset-rows 400000] [--data /path.parquet]
       [--state /path.json] [--out benchmarks/bench_r04_tpu.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROW_SHAPE = (224, 224, 3)
ROW_BYTES = int(np.prod(ROW_SHAPE))


def rss_gb() -> float:
    """Current process anon RSS in GB (0.0 when /proc is unreadable)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1e6  # kB -> GB
    except (OSError, ValueError, IndexError):
        pass
    return 0.0


def stall_watchdog_loop(get_fenced, is_streaming, timeout_s: float,
                        on_stall, sleep_s: float = 10.0,
                        clock=time.monotonic, sleep=time.sleep) -> None:
    """Fire ``on_stall()`` when fenced progress freezes for
    ``timeout_s`` while streaming is active. The round-5 wire stall:
    a fence readback simply never returned (12+ minutes, process
    alive, zero progress) and needed an operator kill — this loop is
    that operator. The timer resets on ANY fenced progress and while
    streaming is inactive — and "streaming" arms only at the FIRST
    drained batch of a pass, so dataset gen, compile, the final
    download AND the resume skip-scan (minutes of reader decode at
    large offsets, zero fenced progress by design) can't
    false-positive. Runs on a daemon
    thread; during a real stall the main thread is BLOCKED inside the
    dead fence, so the state it snapshots is quiescent. Injectable
    clock/sleep for tests; returns when on_stall() returns (the real
    on_stall execv's and never does)."""
    last_rows, last_t = get_fenced(), clock()
    while True:
        sleep(sleep_s)
        if not is_streaming():
            last_rows, last_t = get_fenced(), clock()
            continue
        now_rows = get_fenced()
        if now_rows != last_rows:
            last_rows, last_t = now_rows, clock()
        elif clock() - last_t > timeout_s:
            on_stall()
            return


def ensure_dataset(path: str, rows: int) -> int:
    from sparktorch_tpu.inference import write_rows_parquet

    if os.path.exists(path):
        import pyarrow.parquet as pq

        have = pq.ParquetFile(path).metadata.num_rows
        if have >= rows:
            print(f"dataset: {path} already has {have} rows", flush=True)
            return have
        os.remove(path)
    print(f"dataset: generating {rows} uint8 rows {ROW_SHAPE} -> {path}",
          flush=True)
    rng = np.random.default_rng(0)
    gen_chunk = 512

    def gen():
        done = 0
        while done < rows:
            n = min(gen_chunk, rows - done)
            yield rng.integers(0, 256, (n, *ROW_SHAPE), dtype=np.uint8)
            done += n

    t0 = time.perf_counter()
    total = write_rows_parquet(path, gen(), rows_per_group=gen_chunk)
    print(f"dataset: wrote {total} rows in {time.perf_counter() - t0:.1f}s",
          flush=True)
    return total


def load_state(path: str) -> dict:
    if path and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {"rows_done": 0, "elapsed_s": 0.0, "pass_rows": [],
            "pass_s": [], "restarts": 0}


def save_state(path: str, st: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(st, f)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--dataset-rows", type=int, default=400_000)
    ap.add_argument("--data", default="/root/stream_bench_1m_src.parquet")
    ap.add_argument("--state", default="/root/stream_1m_state.json")
    ap.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_r05_tpu.jsonl"),
    )
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument(
        "--rss-limit-gb", type=float, default=48.0,
        help="exec-restart (resuming from the fenced state) when host "
        "RSS exceeds this (0 disables)",
    )
    ap.add_argument(
        "--stall-timeout-s", type=float, default=600.0,
        help="exec-restart when FENCED progress freezes this long mid-"
        "stream (a fence readback that never returns; 0 disables)",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.inference import BatchPredictor, stream_parquet_predict
    from sparktorch_tpu.models.resnet import resnet50
    from sparktorch_tpu.utils.checkpoint import arm_compile_cache

    arm_compile_cache(min_compile_time_s=0.5)

    # No retry: a backend that cannot initialise fails here, loudly.
    backend = jax.default_backend()
    n_chips = len(jax.devices())
    print(f"backend={backend} devices={n_chips} rss={rss_gb():.1f}GB",
          flush=True)

    have = ensure_dataset(args.data, args.dataset_rows)
    dataset_rows = min(have, args.dataset_rows)

    module = resnet50()
    variables = module.init(
        jax.random.key(0), np.zeros((1, *ROW_SHAPE), np.float32)
    )
    predictor = BatchPredictor(
        module, variables["params"],
        {k: v for k, v in variables.items() if k != "params"},
        chunk=args.chunk,
        preprocess=lambda x: x.astype(jnp.float32) / 255.0,
        # Device-side argmax (the reference's predict_float semantics,
        # torch_distributed.py:112-120): one class id per row on the
        # readback wire, not 1000 logits.
        postprocess=lambda y: jnp.argmax(y, axis=-1).astype(jnp.int32),
    )
    # Timing discipline: everything below that claims a rate ends in
    # a data-dependent scalar readback, float(jnp.sum(...)) — a fence
    # that cannot return before the device work it depends on.
    out = predictor.predict_device(
        np.zeros((args.chunk, *ROW_SHAPE), np.uint8)
    )
    float(jnp.sum(out))  # compile + honest fence

    # Device-resident chip rate via a PAIRED-SIZE slope (the fence
    # round-trip cancels): T(16 chunks) - T(4 chunks) over the extra
    # 12 chunks of pure compute.
    warm = np.random.default_rng(1).integers(
        0, 256, (16 * args.chunk, *ROW_SHAPE), dtype=np.uint8
    )
    xd = jax.device_put(warm)
    float(jnp.sum(predictor.predict_device(xd[: 4 * args.chunk])))  # warm
    t0 = time.perf_counter()
    float(jnp.sum(predictor.predict_device(xd[: 4 * args.chunk])))
    t_small = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(jnp.sum(predictor.predict_device(xd)))
    t_big = time.perf_counter() - t0
    chip_rate = 12 * args.chunk / max(t_big - t_small, 1e-9) / n_chips
    del xd, warm
    print(f"chip rate (device-resident, paired-size slope): "
          f"{chip_rate:.1f} rows/s/chip", flush=True)

    # Predictions accumulate into ONE device buffer (int32 per row =
    # 4 MB at 1M rows) via a donated dynamic_update_slice; the single
    # download happens after the stream, when upload speed no longer
    # matters.
    result_buf = jnp.zeros((args.rows,), jnp.int32)

    _acc = jax.jit(
        lambda buf, vals, off: jax.lax.dynamic_update_slice(
            buf, vals, (off,)
        ),
        donate_argnums=(0,),
    )

    st = load_state(args.state)
    resume_start = int(st["rows_done"])
    print(f"resume state: {resume_start} rows already done", flush=True)
    if resume_start >= args.rows:
        # Re-invoked after completion: nothing to run, and appending a
        # no-work row (with an all-zeros histogram from the fresh
        # buffer) would corrupt the log.
        print(f"already complete ({resume_start} >= {args.rows}); "
              f"nothing to do — see {args.out}", flush=True)
        return
    if resume_start:
        print("note: predictions for pre-resume rows are not retained "
              "across processes (rate metrics are; the final histogram "
              "covers only this process's rows)", flush=True)

    base_elapsed = float(st.get("elapsed_s", 0.0))
    if "exec_ts" in st:
        # A self-restart persisted its wall clock just before execv:
        # everything since — backend re-init retries, model init,
        # compile, the chip-rate probe — is end-to-end wall and must
        # not vanish from elapsed (the 'measured end to end' contract).
        base_elapsed += max(0.0, time.time() - float(st.pop("exec_ts")))
        save_state(args.state, st)
    t_run0 = time.perf_counter()
    last_save = [t_run0]
    nonlocal_buf = [result_buf]
    pending_fence = [None]

    # Two counters: rows_done advances at DISPATCH (it drives the
    # device-buffer offsets), but persisted state only ever records
    # FENCED rows — work whose data-dependent readback completed — so
    # a crash can never mark never-executed rows as done (execution is
    # FIFO: consuming batch k's fence proves every batch <= k ran).
    fenced = [resume_start]

    # Serializes every state mutation/persist between the main thread
    # and the watchdog thread (concurrent writers to the same tmp file
    # could publish truncated JSON and brick every later resume).
    import threading

    state_lock = threading.RLock()

    def snapshot(final: bool = False):
      with state_lock:
        st["elapsed_s"] = base_elapsed + (time.perf_counter() - t_run0)
        persist = dict(st)
        if not final:
            persist["rows_done"] = min(st["rows_done"], fenced[0])
            # Pass accounting is appended from dispatch-side counters;
            # clamp the last entry so the persisted pass_rows never sum
            # past the fenced progress (a crash between a pass's append
            # and its final fence would otherwise skew per-pass rates).
            ps = [int(r) for r in persist.get("pass_rows", [])]
            excess = sum(ps) - persist["rows_done"]
            if excess > 0 and ps:
                ps[-1] = max(0, ps[-1] - excess)
                persist["pass_rows"] = ps
        save_state(args.state, persist)

    # Current pass-segment bookkeeping, visible to the watchdog so a
    # mid-pass restart can close the partial segment's accounting.
    cur_pass = {"start_rows": 0, "t0": 0.0}

    def _do_restart(reason: str):
        """Persist the fenced state (closing the partial pass segment
        so passes still sum to n_rows, and stamping exec_ts so the
        restart's wall stays in elapsed) and exec-restart THIS command
        in place — same pid, same argv; the fresh process resumes
        mid-pass from the state file."""
        state_lock.acquire()  # held until execv (the process dies)
        st["restarts"] = int(st.get("restarts", 0)) + 1
        seg_rows = max(0, min(st["rows_done"], fenced[0])
                       - cur_pass["start_rows"])
        if seg_rows > 0:
            st["pass_rows"].append(seg_rows)
            st["pass_s"].append(
                round(time.perf_counter() - cur_pass["t0"], 2)
            )
        st["exec_ts"] = time.time()
        snapshot()
        print(f"{reason} — exec-restarting at fenced row {fenced[0]}",
              flush=True)
        sys.stdout.flush()
        sys.stderr.flush()
        try:
            os.execv(sys.executable,
                     [sys.executable, os.path.abspath(__file__)]
                     + sys.argv[1:])
        except OSError as exc:
            # A failed execv must not strand state_lock with this
            # (possibly watchdog-thread) caller — the main thread
            # would hang at its next snapshot(). The fenced state was
            # just persisted, so a hard exit keeps the crash-safe
            # contract: rerunning the command resumes from the fence.
            print(f"exec-restart FAILED ({exc}); exiting for external "
                  "resume from the persisted state", flush=True)
            os._exit(17)

    def maybe_restart():
        """The automated leak mitigation (checked at the 30s save
        cadence in drain)."""
        if args.rss_limit_gb and args.rss_limit_gb > 0:
            r = rss_gb()
            if r > args.rss_limit_gb:
                _do_restart(
                    f"rss watchdog: {r:.1f}GB > {args.rss_limit_gb}GB "
                    "(upload-staging leak)"
                )

    # The wire can STALL outright (a fence readback that never
    # returns — observed 12+ minutes frozen); the main thread is stuck
    # inside the dead RPC then, so the stall remedy runs on its own
    # thread.
    streaming = [False]
    if args.stall_timeout_s and args.stall_timeout_s > 0:
        threading.Thread(
            target=stall_watchdog_loop,
            args=(lambda: fenced[0], lambda: streaming[0],
                  args.stall_timeout_s,
                  lambda: _do_restart(
                      f"stall watchdog: no fenced progress for "
                      f"{args.stall_timeout_s:.0f}s (wire stall)"
                  )),
            daemon=True,
        ).start()

    while st["rows_done"] < args.rows:
        pass_start_rows = st["rows_done"]
        cur_pass["start_rows"] = pass_start_rows
        offset_in_pass = st["rows_done"] % dataset_rows
        want = min(dataset_rows - offset_in_pass,
                   args.rows - st["rows_done"])

        def drain(out):
            # `out` is a DEVICE array; park it in the big on-device
            # result buffer. The lag-1 scalar fence keeps dispatch
            # honest AND bounds in-flight device buffers to ~2 reader
            # batches (block_until_ready under-blocks on this rig, so
            # a real data-dependent readback is the only backpressure
            # that works; it costs one round-trip per 1024 rows —
            # ~1-3% of the batch's 15 s of wire time).
            start = st["rows_done"]
            streaming[0] = True  # first drain: fenced progress begins;
            # arming earlier would count the resume skip-scan (minutes
            # at large offsets) as a "stall"
            nonlocal_buf[0] = _acc(nonlocal_buf[0], out, start % args.rows)
            fence, pending_fence[0] = (
                pending_fence[0],
                (jnp.sum(out), start + out.shape[0]),
            )
            if fence is not None:
                float(fence[0])
                fenced[0] = fence[1]
            st["rows_done"] = start + out.shape[0]
            now = time.perf_counter()
            if now - last_save[0] >= 30.0:
                last_save[0] = now
                snapshot()
                rate = st["rows_done"] / max(1e-9, st["elapsed_s"])
                print(f"progress: {st['rows_done']}/{args.rows} rows "
                      f"(cum {rate:.1f} rows/s, rss {rss_gb():.1f}GB)",
                      flush=True)
                maybe_restart()

        t_pass0 = time.perf_counter()
        cur_pass["t0"] = t_pass0
        stats = stream_parquet_predict(
            predictor, args.data, row_shape=ROW_SHAPE, dtype=np.uint8,
            batch_rows=4 * args.chunk, drain=drain,
            skip_rows=offset_in_pass, max_rows=want,
            device_outputs=True,
        )
        streaming[0] = False
        dt_pass = time.perf_counter() - t_pass0
        with state_lock:
            st["pass_rows"].append(st["rows_done"] - pass_start_rows)
            st["pass_s"].append(round(dt_pass, 2))
        snapshot()
        print(f"pass segment: {stats['n_rows']} rows in {dt_pass:.1f}s "
              f"({stats['n_rows']/max(dt_pass,1e-9):.1f} rows/s) "
              f"read_busy={stats['read_busy_s']}s "
              f"predict_busy={stats['predict_busy_s']}s", flush=True)

    # The ONE download: every prediction, after the stream. Included
    # in the wall via the state's elapsed accounting below.
    t_dl = time.perf_counter()
    preds = np.asarray(nonlocal_buf[0])
    dl_s = time.perf_counter() - t_dl
    st["elapsed_s"] = base_elapsed + (time.perf_counter() - t_run0)
    save_state(args.state, st)
    own = preds[resume_start % args.rows : st["rows_done"]]
    head = own[:10000] if own.size else preds[:1]
    print(f"final download: {preds.nbytes/1e6:.1f} MB of predictions "
          f"in {dl_s:.2f}s (class histogram head, this process's rows: "
          f"{np.bincount(head % 10)[:5].tolist()})", flush=True)

    wall = st["elapsed_s"]
    rate = st["rows_done"] / max(wall, 1e-9)
    wire_mb_s = rate * ROW_BYTES / 1e6
    row = {
        "config": "resnet50_inference_stream",
        "unit": "rows/sec end-to-end",
        "backend": backend,
        "n_chips": n_chips,
        "n_rows": st["rows_done"],
        "dataset_rows": dataset_rows,
        "passes": [int(r) for r in st["pass_rows"]],
        "pass_seconds": st["pass_s"],
        "pass_rates": [
            round(r / max(s, 1e-9), 1)
            for r, s in zip(st["pass_rows"], st["pass_s"])
        ],
        "wall_s": round(wall, 1),
        "rows_per_sec": round(rate, 2),
        "steady_rows_per_sec": round(rate, 2),
        "wire_MB_per_sec": round(wire_mb_s, 1),
        "chip_rate_rows_per_sec_per_chip": round(chip_rate, 1),
        "chip_busy_fraction": round(rate / (chip_rate * n_chips), 3),
        "rss_limit_gb": args.rss_limit_gb,
        "auto_restarts": int(st.get("restarts", 0)),
        "wire_dtype": "uint8 (normalize + argmax fused on device)",
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(row), flush=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
