"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload register10m.point --seed 7 \\
        --seconds 50 --trace 0

One process holds one chip. It makes the cell's raw data from ``--seed``,
builds the program's network from it with the program's own builders
(``netbuild.py``), starts the program's NDJSON frontend
(``api.servenet``), warms it up with the cell's traffic from a separate
stream, and then measures for ``--seconds`` seconds while a child process
(``loadgen.py``, no JAX) sends the cell's traffic over loopback TCP.
Afterwards every answer due in the window is compared with the plain
reference (``graphref.py``), which answers from the generator's raw lists
and not from the program's network.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the JAX profiler, with host spans around the
program's layers, and reports the per-layer metrics, the device's busy
time and a breakdown.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and, last, ``checks``: each number compared with its
limit (``at_most`` or ``at_least``): no wrong answer, no error answer,
no lost reply, and at least one answer compared. The same numbers are
the last lines of standard error. Without an
accelerator the run stops with exit code 2 and prints no result;
``--rehearse`` runs the configuration's small rehearsal sizes on any
backend instead, and ends not correct.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import latency  # noqa: E402
import loadplan  # noqa: E402
import netbuild  # noqa: E402
from manifest import Cell  # noqa: E402

JAX_CACHE = BENCH / ".jax_cache"
TRACES = BENCH / ".traces"
LATE_WAIT_S = 75.0  # past the close: the child gives up on a reply at 60 s


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _with_rehearsal(doc: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in doc.items() if k != "rehearsal"}
    if rehearse:
        out.update(doc.get("rehearsal", {}))
    return out


# ---------------------------------------------------------------------------
# Serving the window
# ---------------------------------------------------------------------------


class LoadGen:
    """The load generator child process and its line protocol; a reader
    thread queues the child's lines so that every wait has a timeout."""

    def __init__(self, plan: dict):
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=str(ROOT))
        self._lines: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self._send(json.dumps(plan))

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def _send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def expect(self, event: str, timeout: float | None = None) -> dict:
        try:
            line = self._lines.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(f"no {event!r} from the load generator "
                               f"within {timeout:.0f}s") from None
        if line is None:
            raise RuntimeError(f"load generator exited before {event!r} "
                               f"(code {self.proc.poll()})")
        msg = json.loads(line)
        if msg.get("event") != event:
            raise RuntimeError(f"load generator sent {msg.get('event')!r}, "
                               f"expected {event!r}")
        return msg

    def go(self) -> float:
        self._send("go")
        return float(self.expect("start", timeout=60.0)["t0"])

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._reader.join(timeout=10.0)
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass


def _wire_phase(phase: dict) -> dict:
    out = {k: v for k, v in phase.items() if k not in ("requests", "wire")}
    out["requests"] = phase["wire"]
    return out


def serve_window(net, warm: dict, window: dict, seconds: float, trace: bool,
                 trace_dir: Path, t_start: float) -> dict:
    """Warm up, then measure; -> the records and counter snapshots."""
    import jax

    from repro.core import api, compile_cache

    from spans import Spans

    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(Spans())
        fe = api.servenet(net)
        stack.callback(fe.close)
        host, port = fe.address
        gen = LoadGen({"host": host, "port": port,
                       "warmup": _wire_phase(warm),
                       "window": _wire_phase(window)})
        stack.callback(gen.close)
        t = time.monotonic()
        ready = gen.expect("ready")
        log(f"warm-up: {ready['warmup_done']} requests in "
            f"{time.monotonic() - t:.1f}s, "
            f"{compile_cache.stats()['requests']} programs compiled so far")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        eng0, comp0 = fe.engine.stats, compile_cache.stats()
        adm0 = fe.admission.stats
        t0 = gen.go()
        setup_s = t0 - t_start
        with (jax.profiler.TraceAnnotation("bench.window") if trace
              else contextlib.nullcontext()):
            time.sleep(max(t0 + seconds - time.monotonic(), 0.0))
        eng1, comp1 = fe.engine.stats, compile_cache.stats()
        adm1 = fe.admission.stats
        if trace:
            jax.profiler.stop_trace()
        wait = max(t0 + seconds + LATE_WAIT_S - time.monotonic(), 1.0)
        records = gen.expect("records", timeout=wait)["records"]
        transport = fe.stats["transport"]
        # the peak is read after the window and before the server's state
        # is freed; the reference runs later
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
    return {"records": records, "t0": t0, "setup_s": setup_s,
            "engine": (eng0, eng1), "compiles": (comp0, comp1),
            "admission": (adm0, adm1), "transport": transport,
            "memory_peak_bytes": int(peak)}


# ---------------------------------------------------------------------------
# Judging the window
# ---------------------------------------------------------------------------


def classify(records: list, requests: list[dict]) -> list[dict]:
    """Parse each reply: {"i", "req", "due", "woke", "sent", "recv",
    "status", "result"} with status ok | failed | error."""
    out = []
    for i, due, woke, sent, recv, reply, err in records:
        row = {"i": i, "req": requests[i], "due": due, "woke": woke,
               "sent": sent, "recv": recv, "result": None, "code": None}
        if reply is None:
            row["status"], row["code"] = "failed", f"lost: {err}"
        else:
            msg = json.loads(reply)
            if msg.get("ok") and not msg.get("degraded"):
                row["status"], row["result"] = "ok", msg.get("result")
            elif msg.get("ok"):
                row["status"], row["code"] = "failed", "degraded"
            else:
                code = msg.get("code")
                row["code"] = code
                # load-related refusals fail a request; an error answer
                # where the program should have answered is a wrong answer
                row["status"] = ("failed" if code in
                                 ("shed", "deadline", "in_flight", "closed")
                                 else "error")
                row["error"] = msg.get("error")
        out.append(row)
    return out


def _same(got, want) -> bool:
    if isinstance(want, float):
        return isinstance(got, (int, float)) and float(got) == want
    return got == want


def check(plain, rows: list[dict], log_limit: int = 3) -> dict:
    """Compare every answered row with the plain reference -> counts; the
    reference answer is kept on each row as ``want``."""
    answered = [r for r in rows if r["status"] == "ok"]
    want = plain.answers([r["req"] for r in answered])
    wrong = 0
    for r, w in zip(answered, want):
        r["want"] = w
        if not _same(r["result"], w):
            wrong += 1
            if wrong <= log_limit:
                log(f"MISMATCH {json.dumps(r['req'])}: got "
                    f"{str(r['result'])[:300]} want {str(w)[:300]}")
    return {"checked": len(answered), "wrong": wrong}


# ---------------------------------------------------------------------------
# The cell
# ---------------------------------------------------------------------------


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def prepare(cell: Cell, seed: int, rehearse: bool = False):
    """The cell's raw data from the seed and the program's network built
    from it -> (plain reference, network)."""
    import jax

    cfg = _with_rehearsal(cell.config, rehearse)
    t = time.monotonic()
    plain = cell.config_module().generate(cfg, seed)
    log(f"data: {cell.workload['config']} from seed {seed} in "
        f"{time.monotonic() - t:.1f}s")
    t = time.monotonic()
    net = netbuild.network(plain)
    jax.block_until_ready(net)
    log(f"network: {net.nbytes:,} layer bytes built by the program's "
        f"builders and placed in {time.monotonic() - t:.1f}s")
    return plain, net


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, trace_dir: Path | None = None,
             t_start: float | None = None, mix_update: dict | None = None,
             keep_trace: bool = False, prepared: tuple | None = None) -> dict:
    """One run of one cell -> the result dict (``line`` is what is printed).

    ``mix_update`` overrides parameters of the traffic mix (the knee
    sweep's rates); ``keep_trace`` leaves the profiler's files on disk;
    ``prepared`` is ``prepare``'s result for this seed, which the caller
    keeps (the sweep serves one network at several rates)."""
    import jax

    t_start = time.monotonic() if t_start is None else t_start
    mix = _with_rehearsal(cell.traffic, rehearse)
    mix.update(mix_update or {})
    plain, net = prepared or prepare(cell, seed, rehearse)
    warm = loadplan.phase(plain, mix, seed, loadplan.WARMUP_STREAM,
                          float(mix["warmup_s"]))
    window = loadplan.phase(plain, mix, seed, loadplan.WINDOW_STREAM, seconds)
    trace_dir = trace_dir or TRACES / f"{cell.name}-{seed}"
    served = serve_window(net, warm, window, seconds, trace, trace_dir,
                          t_start)
    del net
    gc.collect()

    rows = classify(served["records"], window["requests"])
    t0 = served["t0"]
    closed = mix["loop"] == "closed"
    in_window = [r for r in rows if r["due"] < t0 + seconds]
    attempted = len(window["requests"]) if not closed else len(in_window)
    lost = attempted - len(in_window) if not closed else 0
    lost += sum(1 for r in in_window if str(r["code"]).startswith("lost"))
    ok = [r for r in in_window if r["status"] == "ok"]
    errors = [r for r in in_window if r["status"] == "error"]
    failed = attempted - len(ok)
    lat_ms = [(r["recv"] - r["due"]) * 1e3 for r in ok]
    late_ms = sorted((r["woke"] - r["due"]) * 1e3 for r in in_window)
    pool_ms = sorted((r["sent"] - r["woke"]) * 1e3 for r in in_window)

    t = time.monotonic()
    verdict = check(plain, in_window)
    ref_s = time.monotonic() - t
    if closed:
        done = sum(1 for r in ok if r["recv"] <= t0 + seconds)
    else:
        done = len(ok)
    done -= verdict["wrong"]

    eng0, eng1 = served["engine"]
    adm0, adm1 = served["admission"]
    codes: dict = {}
    for r in in_window:
        if r["code"]:
            key = r["code"].split(":")[0]
            codes[key] = codes.get(key, 0) + 1
    comp0, comp1 = served["compiles"]
    log(f"window: {attempted} requests due, {len(ok)} answered, "
        f"{failed} failed {codes}; "
        f"{comp1['requests'] - comp0['requests']} programs compiled; "
        f"engine batches "
        f"{_delta(eng0['batches'], eng1['batches'])}, cache hits "
        f"{eng1['cache']['hits'] - eng0['cache']['hits']}")
    log(f"frontend: shed {adm1['shed'] - adm0['shed']}, degraded "
        f"{adm1['degraded'] - adm0['degraded']}, bad_request "
        f"{codes.get('bad_request', 0)}, transport {served['transport']}")
    if late_ms:
        log(f"generator: late p50 {_q(late_ms, 50):.3f} ms, p99 "
            f"{_q(late_ms, 99):.3f} ms, max {late_ms[-1]:.3f} ms; waiting "
            f"for a connection p99 {_q(pool_ms, 99):.3f} ms")
    log(f"reference: {verdict['checked']} answers compared in {ref_s:.1f}s")

    devices = jax.devices()
    dev = devices[0]
    metrics: dict = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": served["memory_peak_bytes"]}
    if not trace:
        values = {
            "setup_s": (served["setup_s"], "s"),
            "p50_ms": (latency.percentile(lat_ms, failed, 50), "ms"),
            "p95_ms": (latency.percentile(lat_ms, failed, 95), "ms"),
            "done_per_s": (done / seconds, "req/s"),
            "device_gb": (served["memory_peak_bytes"] / 1e9, "GB"),
        }
        for m in cell.end_to_end:
            v, unit = values[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": unit}
    else:
        from manifest import load_module

        trace_mod = load_module(BENCH / "trace.py")
        xplane = trace_mod.find_xplane(trace_dir)
        summary = trace_mod.reduce(trace_mod.events(xplane)) if xplane \
            else None
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        window_bytes = sum(plain.request_bytes(r["req"], r.get("want"))
                           for r in ok)
        ctx = Context(engine=served["engine"], compiles=served["compiles"],
                      trace=summary, window_bytes=window_bytes,
                      peaks=_peaks(dev.device_kind, dev.platform),
                      seconds=seconds)
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
            log(f"trace: busy {summary['busy_s']:.4f}s of "
                f"{summary['window_s']:.4f}s; {window_bytes:,} reference "
                f"bytes")

    checks = {
        "wrong_answers": {"value": verdict["wrong"], "at_most": 0},
        "error_answers": {"value": len(errors), "at_most": 0},
        "lost_replies": {"value": lost, "at_most": 0},
        "answers_compared": {"value": verdict["checked"], "at_least": 1},
    }
    sound = all(c["value"] <= c.get("at_most", c["value"])
                and c["value"] >= c.get("at_least", c["value"])
                for c in checks.values())
    on_chip = dev.platform != "cpu"
    line = {"correct": bool(sound and on_chip), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return {"line": line, "sound": sound, "checked": verdict["checked"],
            "rows": rows, "t0": t0}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0) for k in b if b[k] - a.get(k, 0)}


def _q(sorted_vals: list[float], q: float) -> float:
    return sorted_vals[min(int(len(sorted_vals) * q / 100),
                           len(sorted_vals) - 1)]


def _peaks(kind: str, platform: str) -> dict | None:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind in table:
        return table[kind]
    if platform == "cpu":
        return None  # a rehearsal: no device metric is read
    raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        limit = " ".join(f"{k.replace('_', ' ')} {v}" for k, v in c.items()
                         if k != "value")
        log(f"check {name}: {c['value']} ({limit})")
    if not line["correct"] and line["device"]["platform"] == "cpu":
        log("rehearsal on the CPU: not correct by definition")
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small rehearsal sizes on any backend; never "
                         "correct")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
    import jax

    from repro.core import compile_cache

    cell = Cell(args.workload, ROOT)
    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform == "cpu":
            log("bench: no accelerator found (pass --rehearse for a small "
                "run on the CPU)")
            return 2
        if len(devices) < cell.chips:
            log(f"bench: {args.workload} needs {cell.chips} chips, found "
                f"{len(devices)}")
            return 2
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    log(f"compile cache: {compile_cache.enable()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   rehearse=args.rehearse, t_start=T_START)
    print_result(out["line"])
    return 0 if out["line"]["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
