"""The benchmark harness: one cell, one run.

It reads the cell from ``BENCHMARK.json`` and finds everything that belongs
to the cell by name: the configuration file (``bench/configs``), the traffic
mix (``bench/traffic``), one reader per per-layer metric
(``bench/metrics``) and, by the configuration's ``model_type``, the model
family's package (``bench/models``).

The system under test is ``repro.serve.ServeEngine``.  The window drives the
engine's own loop, one pass at a time, exactly as ``ServeEngine.run`` does
(``_admit_phase`` → ``_track_peak`` → ``_finish_ready`` →
``_decode_window`` → ``_finish_ready``), with the clients submitting through
``engine.scheduler.submit``.  Those private methods and ``_new_loop`` /
``_LoopState`` are the benchmark's interface to the program (PERF.md
names them).  The host sees the tokens of a dispatch when the engine call
that ran it returns; that is when the harness stamps them.
"""

from __future__ import annotations

import dataclasses
import json
import time
from collections import deque
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import models
from bench import traffic as traffic_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
QUIET_PASSES = 2            # warm-up ends after this many passes compile nothing


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    conf: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str) -> Cell:
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    conf = json.loads((ROOT / entry["file"]).read_text())
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return Cell(name, w, conf, traffic_mod.load_mix(w["traffic"]), e2e,
                layer)


# ---------------------------------------------------------------------------
# configuration -> program objects
# ---------------------------------------------------------------------------

def run_config(conf: dict, **codec_overrides):
    from repro.configs.base import RunConfig
    from repro.core.collectives import CodecConfig
    sv = conf["serving"]
    if sv["codec"] == "full":
        codec = CodecConfig(cache_block=sv["cache_block"])
    elif sv["codec"] == "off":
        codec = dataclasses.replace(CodecConfig.off(),
                                    cache_block=sv["cache_block"])
    else:
        raise ValueError(f"unknown codec setting {sv['codec']!r}")
    codec = dataclasses.replace(codec, **codec_overrides)
    return RunConfig(codec=codec)


def build_engine(conf: dict, seed: int, **codec_overrides):
    """The engine of one run, serving the seed's weights."""
    from repro.serve.scheduler import ServeEngine
    fam = models.family(conf)
    cfg = fam.model_config(conf)
    sv = conf["serving"]
    params = fam.weights.program_params(fam.Dims.of(conf),
                                        cfg.padded_vocab(sv["tp"]), seed)
    eng = ServeEngine(cfg, run_config(conf, **codec_overrides), tp=sv["tp"],
                      n_slots=sv["n_slots"], max_len=sv["max_len"],
                      params=params,
                      compress_weights=sv["compress_weights"])
    return eng


# ---------------------------------------------------------------------------
# the run record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    uid: int
    client: int
    prompt: np.ndarray
    sent: float
    stamps: List[tuple] = dataclasses.field(default_factory=list)
    seen: int = 0
    slot: Optional[int] = None
    finished: Optional[float] = None
    tokens: Optional[List[int]] = None


@dataclasses.dataclass
class Record:
    """What one window measured; the metric readers read this."""
    t_open: float = 0.0
    t_close: float = 0.0
    spans: List[tuple] = dataclasses.field(default_factory=list)
    reqs: Dict[int, Req] = dataclasses.field(default_factory=dict)
    decode_steps: int = 0
    replay_steps: int = 0
    prompt_tokens: int = 0          # prompt tokens of requests admitted
    replay_tokens: int = 0          # of those, fed through replay steps
    # per decode/replay step: live lengths (after the step) of the slots
    # whose output is used; per admission: (trunk, batch)
    attend_steps: List[np.ndarray] = dataclasses.field(default_factory=list)
    admits: List[tuple] = dataclasses.field(default_factory=list)
    compiles: int = 0
    geometry: dict = dataclasses.field(default_factory=dict)
    trace: Optional[dict] = None
    # (decode/replay steps, admissions) that the traced passes hold
    traced: tuple = (0, 0)
    paused: float = 0.0             # seconds spent stopping the profiler
    peaks: Optional[dict] = None

    @property
    def window_s(self) -> float:
        """The window's length, less the time the profiler took to stop
        (no pass runs then)."""
        return self.t_close - self.t_open - self.paused

    def span_s(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.spans if n == name)

    def traced_steps(self) -> List[np.ndarray]:
        return self.attend_steps[:self.traced[0]]

    def traced_admits(self) -> List[tuple]:
        return self.admits[:self.traced[1]]


class CompileCounter:
    """Counts programs lowered (traced to MLIR), backend compiles, and
    programs loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.compiled += 1
                self.compile_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def total(self) -> int:
        return self.lowered + self.compiled


# ---------------------------------------------------------------------------
# the driver: the engine's own loop, fed by closed-loop clients
# ---------------------------------------------------------------------------

class Driver:
    """Closed-loop clients, one per serving slot, each sending its next
    request when its previous one has finished; one new request enters the
    queue per pass of the loop, and clients that finish together wait their
    turn in FIFO order (their send time is when they enter the queue)."""

    def __init__(self, eng, stream: traffic_mod.Traffic,
                 counter: CompileCounter):
        self.eng = eng
        self.ls = eng._new_loop()
        self.stream = stream
        self.counter = counter
        self.n_clients = eng.n_slots
        self.per_pass = 1
        self.ready: deque = deque()      # clients waiting to send
        self.joined = 0                  # clients that have started
        self.live: Dict[int, Req] = {}
        self.every: Dict[int, Req] = {}
        self.rec = Record()
        self.recording = False
        self._count_admissions()

    def _count_admissions(self):
        """Count, from the arguments the engine passes, the prompt tokens
        that replay and the trunks that prefill inside the window."""
        eng = self.eng
        run_replays, admit_cold = eng._run_replays, eng._admit_cold_batch

        def replays_counted(ls, replays):
            if self.recording and replays:
                tails = {s: len(t) for s, t in replays}
                base = {s: ls.slot_len[s] for s in tails}
                self.rec.replay_tokens += sum(tails.values())
                n = max(tails.values())
                self.rec.replay_steps += n
                for j in range(n):
                    self.rec.attend_steps.append(np.asarray(
                        [base[s] + j + 1 for s in tails if tails[s] > j]))
            return run_replays(ls, replays)

        def admit_counted(ls, batch, slots, trunk, replays):
            if self.recording:
                self.rec.admits.append((trunk, len(batch)))
            return admit_cold(ls, batch, slots, trunk, replays)

        eng._run_replays = replays_counted
        eng._admit_cold_batch = admit_counted

    def _span(self, name, fn, *args):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            out = fn(*args)
        t1 = time.perf_counter()
        if self.recording:
            self.rec.spans.append((name, t0, t1))
        return out, t1

    def _stamp(self, t: float):
        for r in self.live.values():
            got = len(self.ls.emitted.get(r.uid, ()))
            if got > r.seen:
                r.stamps.append((t, got - r.seen))
                r.seen = got

    def _submit(self, n: int):
        from repro.serve.scheduler import Request
        for _ in range(min(n, len(self.ready))):
            client = self.ready.popleft()
            spec = self.stream.next()
            req = Req(spec.uid, client, spec.prompt, time.perf_counter())
            self.eng.scheduler.submit(Request(
                uid=spec.uid, prompt=spec.prompt,
                max_new_tokens=spec.max_new_tokens, eos_id=None,
                stop_seqs=()))
            self.live[spec.uid] = req
            self.every[spec.uid] = req

    def served(self) -> List[Req]:
        """Requests the window served, with their tokens: those it
        finished, and those still in a slot at its close with the tokens
        they have so far (so every busy slot's output can be checked)."""
        out = list(self.rec.reqs.values())
        for r in self.live.values():
            toks = self.ls.emitted.get(r.uid)
            if r.slot is not None and toks:
                out.append(dataclasses.replace(r, tokens=list(toks)))
        return out

    def _finish(self, t: float):
        for res in self.eng._finish_ready(self.ls):
            r = self.live.pop(res.uid)
            r.finished = t
            r.tokens = list(res.tokens)
            self.ready.append(r.client)
            if self.recording:
                self.rec.reqs[r.uid] = r

    def one_pass(self):
        """One pass of ``ServeEngine.run``'s loop, with the clients' sends."""
        eng, ls = self.eng, self.ls
        if self.joined < self.n_clients:           # ramp: one joins a pass
            self.ready.append(self.joined)
            self.joined += 1
        self._submit(self.per_pass)
        queued = [r.uid for r in eng.scheduler.queue]
        _, t = self._span("admit", eng._admit_phase, ls)
        self._stamp(t)
        for slot, q in enumerate(ls.slot_req):
            r = self.live.get(q.uid) if q is not None else None
            if r is not None and r.slot is None:
                r.slot = slot
        if self.recording:
            still = {r.uid for r in eng.scheduler.queue}
            self.rec.prompt_tokens += sum(
                len(self.live[u].prompt) for u in queued
                if u not in still and u in self.live)
        eng._track_peak(ls)
        self._finish(time.perf_counter())
        live = ls.live_slots()
        steps0 = ls.steps
        lens = np.asarray([ls.slot_len[s] for s in live
                           if not ls.done[s]])
        _, t = self._span("decode", eng._decode_window, ls)
        self._stamp(t)
        if self.recording:
            k = ls.steps - steps0
            self.rec.decode_steps += k
            for j in range(k):
                self.rec.attend_steps.append(lens + j + 1)
        self._finish(t)

    def ramp(self, max_passes: int = 100000):
        """Clients join one per pass; then passes run until QUIET_PASSES in
        a row compile nothing."""
        quiet = 0
        for _ in range(max_passes):
            c0 = self.counter.total
            self.one_pass()
            full = self.joined >= self.n_clients
            quiet = quiet + 1 if (full and self.counter.total == c0) else 0
            if quiet >= QUIET_PASSES:
                return
        raise RuntimeError("warm-up never reached a pass without compiles")

    def window(self, seconds: float, trace_dir: Optional[str] = None,
               trace_s: float = 0.0) -> Record:
        """Measure for ``seconds``, ending at the first pass boundary after
        them.  With ``trace_dir``, the profiler traces the passes of the
        first ``trace_s`` seconds (whole passes, so that the steps the
        trace holds are known)."""
        import jax
        self.recording = True
        c0 = self.counter.total
        rec = self.rec
        tracing = trace_dir is not None
        if tracing:
            jax.profiler.start_trace(trace_dir)
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
        rec.t_open = t = time.perf_counter()
        end = t + seconds
        while t < end:
            self.one_pass()
            t = time.perf_counter()
            if tracing and t - rec.t_open >= trace_s:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
                rec.traced = (len(rec.attend_steps), len(rec.admits))
                now = time.perf_counter()
                rec.paused, end, t = now - t, end + now - t, now
        if tracing:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            rec.traced = (len(rec.attend_steps), len(rec.admits))
        rec.t_close = t
        self.recording = False
        rec.compiles = self.counter.total - c0
        return rec


def precompile(eng, mix: dict):
    """Compile every admit, replay and decode program the mix can reach
    (one request admitted per pass), by calling each once on the engine's
    state and dropping the result (the state is not donated, so the engine
    is left as it was)."""
    import jax
    import jax.numpy as jnp
    pr = mix["prompt_tokens"]
    buckets = sorted({eng._bucket_of(n) for n in range(pr["lo"],
                                                       pr["hi"] + 1)})
    ks, k = [], 1
    while k <= eng.max_fuse_steps:
        ks.append(k)
        k *= 2
    for b in buckets:
        jax.block_until_ready(eng._admit_for(b, 1)(
            eng.params, eng.state, jnp.zeros((1, b), jnp.int32),
            jnp.zeros((1,), jnp.int32)))
    s = eng.n_slots
    for k in ks:
        jax.block_until_ready(eng._replay_for(k)(
            eng.params, eng.state, jnp.zeros((k, s, 1), jnp.int32),
            jnp.zeros((k, s), bool)))
        jax.block_until_ready(eng._decode_for(k)(
            eng.params, eng.state, jnp.zeros((s, 1), jnp.int32)))
    return {"admit": [(b, 1) for b in buckets], "replay_decode_steps": ks}


def geometry(eng) -> dict:
    """Shapes of the program's stores that the FLOP/byte functions need:
    bytes of one KV page as the pool stores it, and each packed weight that
    the fused kernel multiplies (bytes per call, calls per step)."""
    import jax
    from repro.core.weights import PackedWeight
    kv = eng.state.kv
    fields = ([kv.signman, kv.planes, kv.dict_syms, kv.esc_pos, kv.esc_raw]
              if kv.signman is not None else [kv.raw_pages])
    # global pool leaves are (tp, layers, pages, ...)
    page = sum(int(np.prod(a.shape[3:])) * a.dtype.itemsize for a in fields)
    packed = []
    flat = jax.tree_util.tree_flatten_with_path(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))[0]
    for path, leaf in flat:
        if not isinstance(leaf, PackedWeight) or leaf.backend == "jax":
            continue
        sm = leaf.signman
        count = int(np.prod(sm.shape[:-2]))
        nbytes = sum(int(a.size) * a.dtype.itemsize
                     for a in (leaf.signman, leaf.planes, leaf.dict_syms))
        packed.append({"k": int(sm.shape[-2]), "n": int(sm.shape[-1]),
                       "bytes": nbytes // count, "count": count,
                       "head": "lm_head" in jax.tree_util.keystr(path)})
    return {"page_bytes": page, "block": eng.run_cfg.codec.cache_block,
            "packed": packed}


# ---------------------------------------------------------------------------
# end-to-end metrics: taken over every request, from the harness's stamps
# ---------------------------------------------------------------------------

def tokens_in_window(rec: Record, reqs) -> int:
    """Output tokens the host received inside the window, all requests."""
    return sum(n for r in reqs for t, n in r.stamps
               if rec.t_open < t <= rec.t_close)
