"""Driver of open-loop PREDICT serving cells (traffic ``"driver":
"serve"``).

Set-up makes a frozen LDA posterior on the device from the seed (topic
rows shaped like the corpus generator's topics, with per-topic counts of
a trained model), registers it in a ``Gateway`` and warms every fold-in
bucket the traffic can reach.  The window sends ``PREDICT LL FOR DOCS $d
USING ARTIFACT ...`` queries, one document each, on a Poisson schedule
fixed in the traffic file, from a pool of client threads, whether or not
earlier queries have finished; each query's latency runs from the time it
was due.  After the window every answer is compared with the reference's
fold-in score of its document.
"""

from __future__ import annotations

import concurrent.futures as cf
import math
import threading
import time

import numpy as np

from bench import corpus as gen
from bench import counts, harness
from bench import reference as ref

ARTIFACT = "lda"
TENANT = "reader"


def posterior_table(cfg: dict, maps, seed: int):
    """The frozen ``(K, V)`` topic concentrations, made on the device in
    one jitted call: ``beta + n_k * p_k(w) * u`` with ``p_k`` the
    generator's topic ``k``, ``n_k`` its share of the source corpus's
    tokens and ``u`` uniform noise on ``[0.5, 1.5)``."""
    import jax
    import jax.numpy as jnp
    k, v = cfg["K"], cfg["V"]
    perm, a, b = (np.asarray(x) for x in maps)
    per_topic = cfg["source_tokens"] / k

    @jax.jit
    def make(key, perm, a, b):
        r = jnp.arange(v, dtype=jnp.int32)
        hi, lo = r // 512, r % 512
        a_, b_ = a[:, None], b[:, None]
        # (a * r + b) mod v without overflowing int32
        idx = ((a_ * hi % v) * 512 + a_ * lo + b_) % v
        words = perm[idx]
        w = (r + 1).astype(jnp.float32) ** -cfg["zipf_s"]
        w = w / w.sum()
        u = jax.random.uniform(key, (k, v), jnp.float32, 0.5, 1.5)
        rows = jnp.broadcast_to(jnp.arange(k)[:, None], (k, v))
        return jnp.zeros((k, v), jnp.float32).at[rows, words].set(
            cfg["beta"] + per_topic * w[None, :] * u)

    return make(jax.random.PRNGKey(seed), jnp.asarray(perm, jnp.int32),
                jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32))


def buckets(max_docs: int, max_len: int, min_cap: int) -> list:
    """Every power-of-two token bucket a batch of at most ``max_docs``
    documents of at most ``max_len`` tokens can land in."""
    top = 1 << math.ceil(math.log2(max_docs * max_len))
    out, c = [], min_cap
    while c <= top:
        out.append(c)
        c *= 2
    return out


class Schedule:
    """The window's queries: due times (s from the window's start) and
    documents."""

    def __init__(self, cfg: dict, tr: dict, seed: int, seconds: float,
                 maps):
        rng = gen.rng_for(seed, 2)
        n = max(1, round(tr["rate_per_s"] * seconds))
        gaps = gen.exponential_gaps(n, 1.0 / tr["rate_per_s"], rng)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        self.due = due[due < seconds]
        lengths = gen.lognormal_lengths(len(self.due),
                                        cfg["mean_doc_tokens"],
                                        cfg["doc_length_sigma"], rng,
                                        tr["max_doc_tokens"])
        docs = gen.documents(lengths, cfg["K"], cfg["V"], cfg["alpha"],
                             cfg["zipf_s"], rng, maps)
        self.lengths = lengths
        self.offsets = np.concatenate([[0], np.cumsum(lengths)])
        self.tokens = docs["tokens"]

    def doc(self, i: int) -> np.ndarray:
        return self.tokens[self.offsets[i]:self.offsets[i + 1]]


class Service:
    """The served artifact behind a ``Gateway``, warmed for a traffic
    mix; a context manager that stops the gateway and its clients."""

    def __init__(self, ctx):
        from repro.gateway import Gateway
        from repro.gateway.admission import TenantQuota
        from repro.query import Posterior
        from repro.query.foldin import FoldInConfig

        cfg, tr = ctx.cfg, ctx.traffic
        self.ctx, self.cfg, self.tr = ctx, cfg, tr
        k, v = cfg["K"], cfg["V"]
        self.maps = gen.topic_maps(k, v, gen.rng_for(ctx.seed, 0))
        self.phi = posterior_table(cfg, self.maps, ctx.seed)
        post = Posterior(posteriors={"phi": self.phi}, model="lda",
                         params={"alpha": cfg["alpha"], "beta": cfg["beta"],
                                 "K": k, "V": v},
                         local=("theta",), observed=("x",), meta={})
        self.text = f"PREDICT LL FOR DOCS $d USING ARTIFACT '{ARTIFACT}'"
        fold_cfg = FoldInConfig(local_iters=tr["foldin_local_iters"])
        self.gw = Gateway(foldin_config=fold_cfg,
                          default_quota=TenantQuota(rate=1e9, burst=1e9),
                          max_batch_docs=tr["max_batch_docs"],
                          max_delay_s=tr["max_delay_s"])
        self.pool = cf.ThreadPoolExecutor(max_workers=tr["clients"],
                                          thread_name_prefix="bench-client")
        self.gw.register(ARTIFACT, post)
        self.entry = self.gw.registry.get(ARTIFACT)
        if ctx.traced:
            self.entry.server._dispatch = harness.span(
                "bench.dispatch", self.entry.server._dispatch)
        # warm every bucket: one request per bucket, its documents filling
        # the bucket to the top
        rng = gen.rng_for(ctx.seed, 3)
        for cap in buckets(tr["max_batch_docs"], tr["max_doc_tokens"],
                           fold_cfg.min_cap):
            n_docs = -(-cap // tr["max_doc_tokens"])
            lens = np.full(n_docs, cap // n_docs, np.int64)
            lens[: cap - int(lens.sum())] += 1
            toks = rng.integers(0, v, int(lens.sum())).astype(np.int32)
            self.gw.query(self.text, params={"d": {"values": toks,
                                                   "lengths": lens}},
                          tenant=TENANT, timeout_s=600)
        # start every client thread now: the pool starts them one a
        # submit, and the window's first queries would wait for that
        ready = threading.Barrier(tr["clients"] + 1)
        for _ in range(tr["clients"]):
            self.pool.submit(ready.wait, 60)
        ready.wait(60)
        log = ctx.compile_log
        harness.say(f"warmed {self.entry.foldin.compiled_buckets} fold-in "
                    f"buckets; {log.compiles} programs loaded, {log.hits} "
                    f"from the persistent cache, {log.seconds:.1f} s "
                    f"compiling or loading")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.pool.shutdown(wait=True, cancel_futures=True)
        self.gw.stop()

    def drive(self, sched: "Schedule", seconds: float, traced: bool,
              before_window=None) -> dict:
        """Send ``sched``'s queries on time for ``seconds``; wait for the
        stragglers; return latencies (from the due time), answers and the
        server's counters at the window's two ends."""
        tr = self.tr
        n = len(sched.due)
        lat = np.full(n, np.nan)
        done_at = np.full(n, np.nan)
        late = np.zeros(n)
        answers: dict = {}
        errors: list = []
        lock = threading.Lock()

        def ask(i: int, t_due: float):
            try:
                r = self.gw.query(self.text, params={"d": {
                    "values": sched.doc(i),
                    "lengths": np.array([sched.lengths[i]])}},
                    tenant=TENANT, timeout_s=tr["deadline_s"])
                t = time.perf_counter()
                with lock:
                    done_at[i] = t
                    lat[i] = t - t_due
                    answers[i] = np.asarray(r.value["doc_ll"], np.float64)
            except Exception as e:          # counted as failed
                with lock:
                    errors.append(f"query {i}: {type(e).__name__}: {e}")

        stats = self.entry.server.stats
        win = harness.Window()
        compiles0 = self.ctx.compile_log.compiles
        if before_window is not None:
            before_window()
        futs = []
        with harness.window(traced, win):
            s0 = stats()
            for i, d in enumerate(sched.due):
                t_due = win.t0 + d
                wait = t_due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late[i] = time.perf_counter() - t_due
                futs.append(self.pool.submit(ask, i, t_due))
            wait = win.t0 + seconds - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            win.t1 = time.perf_counter()
            s1 = stats()
            backlog = sum(1 for f in futs if not f.done())
        cf.wait(futs, timeout=tr["deadline_s"] + 60)
        for e in errors[:5]:
            harness.say(e)
        return {"win": win, "lat": lat, "done_at": done_at, "late": late,
                "answers": answers, "s0": s0, "s1": s1, "backlog": backlog,
                "compiles": self.ctx.compile_log.compiles - compiles0}


def run(ctx) -> dict:
    cfg, tr, seconds = ctx.cfg, ctx.traffic, ctx.seconds
    k, v = cfg["K"], cfg["V"]
    setup = {}

    def mark():
        setup["s"] = time.perf_counter() - ctx.t_start

    with Service(ctx) as svc:
        sched = Schedule(cfg, tr, ctx.seed, seconds, svc.maps)
        w = svc.drive(sched, seconds, ctx.traced, before_window=mark)
        device = harness.device_info(ctx.devs)
    phi = svc.phi
    del svc
    win, lat = w["win"], w["lat"]
    failed = int(np.isnan(lat).sum())
    answered_in_window = int(np.sum(w["done_at"] <= win.t1))
    lat_all = np.where(np.isnan(lat), tr["deadline_s"], lat)
    harness.say(f"{len(sched.due)} queries; generator lateness: median "
                f"{np.median(w['late']) * 1e3:.3f} ms, max "
                f"{np.max(w['late']) * 1e3:.3f} ms; {failed} failed; "
                f"{w['backlog']} unanswered at the close; {w['compiles']} "
                f"compiles in the window")

    t_ref = time.perf_counter()
    readings = check_answers(ctx, sched, w["answers"], phi)
    harness.say(f"reference check took {time.perf_counter() - t_ref:.1f} s")

    s0, s1 = w["s0"], w["s1"]
    d_batches = s1["batches"] - s0["batches"]
    d_docs = s1["docs"] - s0["docs"]
    d_tokens = s1["tokens"] - s0["tokens"]
    passes = tr["foldin_local_iters"] + 1
    plate = None
    if d_batches:
        plate = counts.zstats(d_tokens, k, v, d_docs)
        per_call = counts.zstats(0, k, v, 0)
        plate = {x: passes * (plate[x] + (d_batches - 1) * per_call[x])
                 for x in plate}
    return {
        "attempted": len(sched.due), "failed": failed,
        "e2e": {"query_p95_ms": float(np.percentile(lat_all, 95)) * 1e3,
                "queries_per_s": answered_in_window / win.seconds,
                "setup_s": setup["s"]},
        "layer_run": {
            "trace": win.trace, "window_s": win.seconds,
            "batches": d_batches, "docs": d_docs,
            "compiles_in_window": w["compiles"],
            "device_kind": device["kind"], "n_devices": len(ctx.devs),
            "work": {"zstats": plate, "window": plate}},
        "device": device, "readings": readings,
    }


def check_answers(ctx, sched: Schedule, answers: dict, phi) -> dict:
    """Every answer against the reference's fold-in score of its
    document: the largest gap as a share of the reference's score."""
    import jax.numpy as jnp
    if not answers:
        return {"doc_ll_gap": None}
    ids = np.array(sorted(answers))
    lens = sched.lengths[ids]
    rows = np.repeat(np.arange(len(ids)), lens)
    words = np.concatenate([sched.doc(i) for i in ids])
    want = np.asarray(ref.local_scores(
        phi, rows, words, len(ids), ctx.traffic["foldin_local_iters"],
        ctx.cfg["alpha"], jnp.dtype(ctx.reference_dtype)), np.float64)
    if any(len(answers[i]) != 1 for i in ids):
        return {"doc_ll_gap": None}
    got = np.array([answers[i][0] for i in ids])
    return {"doc_ll_gap": float(np.max(np.abs(got - want) / np.abs(want)))}
