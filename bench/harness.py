"""One run of one cell: build, warm up, drive the traffic for the window,
drain, read the metrics, check the answers against the plain reference.

The configuration, the traffic mix and each per-layer metric are files of
their own, found by name:

- ``bench/configs/<config>.json``: the model's sizes and how it is served;
  its ``family`` names ``bench/adapters/<family>.py`` (builds the system
  under test) and ``bench/refs/<family>.py`` (weights from the seed and
  the plain reference);
- ``bench/traffic/<mix>.json``: parameters for ``bench/loadgen.py``;
- ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns the metric
  or None where the run has nothing for it to read.

Set-up builds the weights on the device, loads every decode program the
window can use (:func:`table_plan`) and every admission shape, then runs
the traffic's own warm-up part; a program built inside the window makes
the run not correct.

The server runs in its own thread, calling ``run_once`` back to back;
the client (this thread) submits each request when it is due, and times
it from then: first token when its output first holds a token, done
when its ``done`` event is set.
"""
from __future__ import annotations

import bisect
import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from bench import loadgen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
POLL_S = 0.001
# an open-loop trace starts this long before the window: starting the
# profiler holds up the client thread for about a tenth of a second
TRACE_LEAD_S = 1.0
DRAIN_LIMIT_S = 90.0             # past the window's close
REF_REQUESTS = 6                 # requests the reference checks per run
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def load_json(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Tracked:
    """A request as the client sees it."""
    spec: loadgen.Spec
    due: float                      # host clock, seconds
    sent: float = 0.0
    first: float | None = None
    done: float | None = None
    req: object = None


class CompileWatch:
    """Counts compile events (traces, backend compiles, cache loads)
    with their host-clock times, from JAX's monitoring hooks."""

    def __init__(self):
        import jax
        self.events: list[tuple[float, str, float, str]] = []
        self._lock = threading.Lock()

        def listen(name, secs, fun_name="", **_):
            if name in COMPILE_EVENTS:
                with self._lock:
                    self.events.append((time.perf_counter(), name, secs,
                                        fun_name))
        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, t0: float, t1: float) -> dict:
        with self._lock:
            ev = [e for e in self.events if t0 <= e[0] < t1]
        compiled = [e[3] for e in ev if e[1] == COMPILE_EVENTS[1]]
        return {"traced": sum(e[1] == COMPILE_EVENTS[0] for e in ev),
                "compiled": len(compiled),
                "compiled_names": sorted(set(compiled)),
                "loaded": sum(e[1] == COMPILE_EVENTS[2] for e in ev),
                "seconds": sum(e[2] for e in ev
                               if e[1] != COMPILE_EVENTS[0])}


def _pow2(n: int, least: int = 1) -> int:
    b = least
    while b < n:
        b *= 2
    return b


def table_plan(longest: int, median: int, *, page: int, slots: int,
               pages: int, max_seq: int, block: int) -> list[dict]:
    """The warm-up that reaches every decode program the window can use.

    The server builds one decode program per page-table width (a power
    of two of the longest live request's pages) and per bucket of table
    entries that change between two blocks (a power of two from 4).  In
    the window the width runs from the one the traffic's median prompt
    needs up to the one ``max_seq`` needs, and a bucket up to the one
    the whole pool fills.  For each width a holder request sets it (the
    first block at a new width rebuilds the table), then each step is
    one decode block: a plain one (the holder alone: bucket 4), and one
    burst per larger bucket, whose requests (prompts of a power of two
    from 8 up to the traffic's longest, the lengths admission pads to;
    two output tokens, so they leave after one block) take about 3/4 of
    the bucket's entries.  A burst's block also clears the rows
    of the burst before it, which can add or cancel up to that many
    entries, so the buckets go in two runs, each four times the last
    (16, 64, ... and 8, 32, ...), and the burst sizes keep the change
    inside the bucket whichever way the rows fall; a plain block after
    each run clears its last burst.  Every number here comes from the
    configuration and the traffic.  Starting at the median prompt's
    width assumes the window always holds a request that long, as it
    does when tens of requests are live; a cell whose window can hold
    only shorter ones builds a program inside it and is not correct.
    """
    def need(tokens):
        return -(-tokens // page)

    lens = [8 << i for i in range(max(longest // 8, 1).bit_length())]
    widths, caps = [], []
    w = _pow2(need(median + 1))
    while w <= _pow2(need(max_seq)):
        widths.append(w)
        w *= 2
    c = 4
    while c <= _pow2(pages):
        caps.append(c)
        c *= 2
    runs = [caps[2::2], caps[1::2]]
    plain = {"bucket": 4, "prompt": 0, "requests": 0, "entries": 0}
    plan = []
    for w in widths:
        holder_new = 1 + block * (len(caps) + 4)
        fits = [n for n in lens if need(n + 1) > w // 2
                and need(n + holder_new) <= w and n + holder_new <= max_seq]
        if not fits:
            continue
        holder = fits[0]
        free = pages - need(holder + holder_new)
        steps = [dict(plain, bucket="table"), plain]
        for run in runs:
            prev = 0
            for c in run:
                lo, hi = c // 2 + prev + 1, c - prev - 1
                best = None
                for n in sorted(lens, reverse=True):
                    p = need(n + 1)
                    k = -(-lo // p)
                    if (p <= w and k * p <= hi and k <= slots - 1
                            and k * p <= free):
                        best = {"bucket": c, "prompt": n, "requests": k,
                                "entries": k * p}
                        break
                if best is not None:
                    steps.append(best)
                    prev = best["entries"]
            steps.append(dict(plain, bucket=f"clear {prev}"))
        plan.append({"width": w, "holder": holder, "holder_new": holder_new,
                     "steps": steps})
    return plan


class ServerThread:
    """Calls ``run_once`` back to back until stopped; an exception
    stops the loop and is raised again by :meth:`check`."""

    def __init__(self, server):
        self.server = server
        self.stop = threading.Event()
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name="bench-server")

    def _loop(self):
        try:
            while not self.stop.is_set():
                t = time.perf_counter()
                self.server.run_once()
                if time.perf_counter() - t < POLL_S:
                    time.sleep(POLL_S / 2)
        except BaseException as e:          # re-raised in the client
            self.error = e
            self.stop.set()

    def start(self):
        self.thread.start()

    def check(self):
        if self.error is not None:
            raise RuntimeError("server loop failed") from self.error

    def join(self):
        self.stop.set()
        self.thread.join(timeout=DRAIN_LIMIT_S)
        if self.thread.is_alive():
            raise RuntimeError("server loop did not stop")
        self.check()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Run:
    def __init__(self, cell: dict, config: dict, traffic: dict, *, seed: int,
                 seconds: float, trace: bool, log,
                 trace_dir: Path | None = None):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.log = log
        self.trace_dir = trace_dir
        fam = config["family"]
        self.adapter = importlib.import_module(f"bench.adapters.{fam}")
        self.ref = importlib.import_module(f"bench.refs.{fam}")
        self.sizes = self.ref.sizes(config)
        self.facts: dict = {}

    # ----- set-up ------------------------------------------------------------
    def build(self):
        import jax
        self.watch = CompileWatch()
        t = time.perf_counter()
        self.model, params, kinds = self.adapter.build(
            self.config, self.seed, self.log)
        want = self.config["serving"]["weights_tier"]
        if want == "pinned_host" and kinds != ["pinned_host"]:
            raise RuntimeError(f"layer weights in {kinds}, not pinned_host: "
                               f"this is not the remote-tier configuration")
        self.facts["weights_s"] = time.perf_counter() - t
        self.server = self.adapter.server(self.model, params,
                                          self.config, self.seed)
        self.dev = jax.devices()[0]
        self.specs = loadgen.specs(self.traffic, self.seconds)
        self.vocab = self.sizes["vocab"]

    def warm_shapes(self):
        """One request per prompt length the traffic draws, so every
        admission shape compiles before the stream starts."""
        t = time.perf_counter()
        lens = sorted({s.prompt_len for s in self.specs})
        reqs = [self._warm_submit(n, 2) for n in lens]
        self._warm_serve(reqs)
        self.facts["warm_shapes_s"] = time.perf_counter() - t
        self.facts["warm_prompt_lengths"] = lens

    def warm_tables(self):
        """Load every decode program the window can use, through the
        server's own calls (:func:`table_plan`), one decode block per
        ``run_once``; the programs built or loaded at each step go to
        the facts."""
        t = time.perf_counter()
        srv = self.config["serving"]
        plan = table_plan(
            max(s.prompt_len for s in self.specs),
            int(np.median([s.prompt_len for s in self.specs])),
            page=srv["page_size"], slots=srv["slots"],
            pages=srv["num_pages"] - 1, max_seq=srv["max_seq"],
            block=srv["block_size"])
        seen = []
        for phase in plan:
            holder = self._warm_submit(phase["holder"], phase["holder_new"])
            for step in phase["steps"]:
                reqs = [self._warm_submit(step["prompt"], 2)
                        for _ in range(step["requests"])]
                seen.append([phase["width"], step["bucket"],
                             *self._warm_step()])
                self._warm_check(reqs)
            self._warm_serve([holder])
        self.facts["warm_tables_s"] = time.perf_counter() - t
        self.facts["warm_tables"] = seen

    def _warm_submit(self, n: int, new: int):
        rng = np.random.default_rng([self.seed, 4, n])
        return self.server.submit(
            rng.integers(1, self.vocab, size=n, dtype=np.int32),
            max_new_tokens=new)

    def _warm_step(self) -> list:
        """One decode block; the table entries it changed and the
        programs built or loaded for it."""
        t, n = time.perf_counter(), self.server.stats["table_delta_entries"]
        self.server.run_once(max_blocks=1)
        got = self.watch.between(t, time.perf_counter())
        return [self.server.stats["table_delta_entries"] - n,
                got["compiled"]]

    @staticmethod
    def _warm_check(reqs):
        bad = [r.uid for r in reqs if not r.done.is_set()
               or r.error is not None]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")

    def _warm_serve(self, reqs):
        while not all(r.done.is_set() for r in reqs):
            self.server.run_once()
        self._warm_check(reqs)

    # ----- traffic -----------------------------------------------------------
    def _submit(self, tr: Tracked):
        toks = loadgen.prompt_tokens(tr.spec, self.vocab, self.seed)
        tr.sent = time.perf_counter()
        tr.req = self.server.submit(toks, max_new_tokens=tr.spec.output_len)

    @staticmethod
    def _poll(tr: Tracked, now: float) -> bool:
        """Record first token / completion; True once done."""
        r = tr.req
        if tr.first is None and len(r.output) >= 1:
            tr.first = now
        if r.done.is_set():
            if tr.first is None:
                tr.first = now
            tr.done = now
            return True
        return False

    def _snap(self) -> dict:
        """The time, the server's counters and the output tokens each
        sent request holds (by spec index), for the per-layer readers."""
        out = {t.spec.index: len(t.req.output) for t in self.tracked
               if t.req}
        return {"t": time.perf_counter(), "out": out,
                "stats": dict(self.server.stats)}

    def drive(self):
        """Run the stream: warm-up part, window, drain."""
        import jax
        self.tracked: list[Tracked] = []
        thread = ServerThread(self.server)
        thread.start()
        open_loop = self.traffic["loop"] == "open"
        clients = self.traffic.get("clients", 0)
        t_stream = time.perf_counter()
        if open_loop:
            self.tracked = [Tracked(s, t_stream + s.due_s) for s in self.specs]
            t0 = t_stream + self.traffic["warmup_s"]
        else:
            t0 = None
        pending = list(self.tracked) if open_loop else []
        pool = iter(self.specs)
        inflight: list[Tracked] = []
        t1 = None
        marks: dict = {}
        finished_tokens = 0
        self.received = [(t_stream, 0)]
        while True:
            thread.check()
            now = time.perf_counter()
            if (self.trace and "p0" not in marks and t0 is not None
                    and now >= t0 - (TRACE_LEAD_S if open_loop else 0)):
                self._start_trace()
                marks["p0"] = self._snap()
            if t0 is not None and t1 is None and now >= t0:
                t1 = t0 + self.seconds
                marks["w0"] = self._snap()
            if t1 is not None and "w1" not in marks and now >= t1:
                marks["w1"] = self._snap()
                if self.trace:
                    marks["p1"] = marks["w1"]
                    jax.profiler.stop_trace()
            closing = "w1" in marks
            if open_loop:
                while pending and pending[0].due <= now:
                    tr = pending.pop(0)
                    if closing and tr.due >= t1:
                        pending = []
                        break
                    self._submit(tr)
                    inflight.append(tr)
            elif not closing:
                while len(inflight) < clients:
                    tr = Tracked(next(pool), now)
                    self._submit(tr)
                    inflight.append(tr)
                    self.tracked.append(tr)
            now = time.perf_counter()
            still = []
            for tr in inflight:
                if self._poll(tr, now):
                    finished_tokens += len(tr.req.output)
                else:
                    still.append(tr)
            inflight = still
            got = finished_tokens + sum(len(tr.req.output) for tr in inflight)
            if got != self.received[-1][1]:
                self.received.append((now, got))
            if (not open_loop and t0 is None
                    and sum(t.first is not None for t in self.tracked)
                    >= clients):
                t0 = now
            if closing and not inflight and not pending:
                break
            if closing and now - marks["w1"]["t"] > DRAIN_LIMIT_S:
                break
            if open_loop and pending and not inflight:
                time.sleep(min(max(pending[0].due - now, 0), POLL_S))
            else:
                time.sleep(POLL_S)
        self.stranded = len(inflight)
        thread.join()
        marks.setdefault("p0", marks["w0"])
        marks.setdefault("p1", marks["w1"])
        self.marks = marks
        self.window = (t0, t1)
        self.facts["window_opened_late_s"] = marks["w0"]["t"] - t0
        self.facts["compiles_in_window"] = self.watch.between(t0, t1)
        self.facts["program_compiles"] = self.server.stats["compiles"]

    def _start_trace(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)

    # ----- results -----------------------------------------------------------
    def window_requests(self) -> list[Tracked]:
        """The requests due in the window: by the schedule, not by when
        the client thread got round to marking the window."""
        t0, t1 = self.window
        return [t for t in self.tracked if t0 <= t.due < t1]

    def received_by(self, t: float) -> int:
        """Output tokens the client had seen by host time ``t``."""
        i = bisect.bisect_right([x[0] for x in self.received], t)
        return self.received[i - 1][1]

    def counters(self) -> dict:
        """The server's counters over the profiled span (the window
        where no trace is taken)."""
        a, b = self.marks["p0"]["stats"], self.marks["p1"]["stats"]
        return {k: b[k] - a[k] for k in b
                if isinstance(b[k], (int, float)) and k in a}

    def free_program(self):
        import jax
        # the CPU backend keeps no allocator statistics
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        self.facts["memory_peak_bytes"] = (
            max(peaks) if None not in peaks else None)
        self.server = None
        self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    def reference_sample(self) -> list[Tracked]:
        """The longest finished window request and others drawn from
        the seed."""
        done = [t for t in self.window_requests()
                if t.done is not None and t.req.error is None]
        if not done:
            return []
        longest = max(done, key=lambda t: (t.spec.prompt_len
                                           + len(t.req.output), t.spec.index))
        rest = [t for t in done if t is not longest]
        rng = np.random.default_rng([self.seed, 5])
        pick = rng.choice(len(rest), size=min(REF_REQUESTS - 1, len(rest)),
                          replace=False) if rest else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def reference_gaps(self, sample: list[Tracked],
                       precisions=("float32",)) -> dict:
        """For each precision: the widest gap by which a served token's
        float32 reference logit lies below the reference's best, where
        the token is the served one ("float32") or the control's own
        first choice at that position (any other precision)."""
        import jax
        srv = self.config["serving"]
        omax = loadgen.max_lengths(self.traffic)[1]
        width = srv["max_seq"]
        n = REF_REQUESTS
        tokens = np.zeros((n, width), np.int32)
        at = np.zeros((n, omax), np.int32)
        served = np.zeros((n, omax), np.int32)
        mask = np.zeros((n, omax), bool)
        for i, t in enumerate(sample):
            prompt = loadgen.prompt_tokens(t.spec, self.vocab, self.seed)
            out = np.asarray(t.req.output, np.int32)
            seq = np.concatenate([prompt, out])
            tokens[i, :len(seq)] = seq
            k = len(out)
            at[i, :k] = len(prompt) - 1 + np.arange(k)
            served[i, :k] = out
            mask[i, :k] = True
        params = self.ref.make_weights(self.sizes, self.seed)
        best, _, got = self.ref.logit_table(self.sizes, params, tokens, at,
                                            served)
        gaps = {"float32": float(np.max((best - got)[mask]))}
        for p in precisions:
            if p == "float32":
                continue
            _, arg, _ = self.ref.logit_table(self.sizes, params, tokens, at,
                                             served, precision=p)
            _, _, chosen = self.ref.logit_table(self.sizes, params, tokens,
                                                at, arg)
            gaps[p] = float(np.max((best - chosen)[mask]))
        del params
        gc.collect()
        jax.clear_caches()
        self.facts["reference_tokens"] = int(mask.sum())
        self.facts["reference_requests"] = len(sample)
        return gaps


def e2e_metrics(run: Run) -> dict:
    """The end-to-end metrics, by the host clock, and the device's
    memory peak."""
    win = run.window_requests()
    t0, t1 = run.window
    out = {}
    ttft = [(t.first - t.due) if t.first is not None else float("inf")
            for t in win]
    tpot = [(t.done - t.first) / (len(t.req.output) - 1) * 1e3
            for t in win if t.done is not None and len(t.req.output) > 1]
    if ttft:
        out["ttft_p50_s"] = (float(np.percentile(ttft, 50)), "s")
        run.facts["ttft_s_p50_p80_p90"] = [
            float(np.percentile(ttft, q)) for q in (50, 80, 90)]
    if tpot:
        out["tpot_p50_ms"] = (float(np.percentile(tpot, 50)), "ms")
        run.facts["tpot_ms_p50_p80_p90"] = [
            float(np.percentile(tpot, q)) for q in (50, 80, 90)]
    out["output_tok_s"] = ((run.received_by(t1) - run.received_by(t0))
                           / (t1 - t0), "tokens/s")
    if run.facts["memory_peak_bytes"] is not None:
        out["hbm_peak_gb"] = (run.facts["memory_peak_bytes"] / 1e9, "GB")
    return out


def run_cell(cell: dict, config: dict, traffic: dict, *, seed: int,
             seconds: float, trace: bool, metrics: list[str],
             limits: dict, log, t_process: float,
             control: str | None = None,
             trace_dir: Path | None = None) -> dict:
    """Build, warm, drive and check one run; returns the result line's
    content (without ``device``) and the numbers compared.

    With ``control`` (a precision of the reference, such as ``"fp8"``)
    the tokens judged are the control's first choices at the served
    positions in place of the served tokens, so ``correct`` is the
    control's verdict; ``gaps`` holds both readings."""
    run = Run(cell, config, traffic, seed=seed, seconds=seconds, trace=trace,
              log=log, trace_dir=trace_dir or Path(tempfile.mkdtemp(prefix="bench-")))
    run.build()
    run.warm_tables()
    run.warm_shapes()
    run.drive()
    setup_s = run.window[0] - t_process
    run.free_program()
    win = run.window_requests()
    failed = [t for t in win if t.done is None or t.req.error is not None
              or len(t.req.output) != t.spec.output_len]
    values = {}
    if trace:
        ctx = dict(run=run, trace=None)
        from bench import trace as tr
        ctx["trace"] = tr.reduce_dir(
            run.trace_dir, run.marks["p1"]["t"] - run.marks["p0"]["t"])
        if trace_dir is None:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
        for name in metrics:
            v = load_metric(name)(ctx)
            if v is not None:
                values[name] = v
        breakdown = ctx["trace"].breakdown()
        device_extra = {"busy_s": ctx["trace"].busy_s,
                        "window_s": ctx["trace"].window_s}
    else:
        e2e = e2e_metrics(run)
        e2e["setup_s"] = (setup_s, "s")
        values = {k: v for k, v in e2e.items() if k in metrics}
        breakdown, device_extra = None, {}
    t = time.perf_counter()
    sample = run.reference_sample()
    judged = control or "float32"
    gaps = run.reference_gaps(sample, ("float32",) + ((control,) if control
                                                      else ())) \
        if sample else {}
    run.facts["reference_s"] = time.perf_counter() - t
    compared = {"served_gap": [gaps.get(judged, float("inf")),
                               limits["served_gap"]],
                "failed_requests": [len(failed), 0],
                "stranded_requests": [run.stranded, 0],
                "programs_built_in_window": [
                    run.facts["compiles_in_window"]["compiled"], 0]}
    correct = all(v <= lim for v, lim in compared.values()) and bool(win)
    run.facts["setup_s"] = setup_s
    return {"correct": correct, "attempted": len(win), "failed": len(failed),
            "metrics": values, "compared": compared, "gaps": gaps,
            "breakdown": breakdown, "device_extra": device_extra,
            "facts": run.facts, "run": run}
