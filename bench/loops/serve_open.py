"""Open loop of ``fit`` requests to a ``SessionServer``.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``rate_per_s``: offered load, fixed in the mix;
* ``tenants``: tenants, tenant t on the configuration's
  ``serve_families[t mod len]`` plan (all on the configuration's graph,
  default combiner), so both plans have hot and cold tenants;
* ``zipf_s``: tenant t sends a share proportional to 1 / (t + 1)^s;
* ``rows``: sample rows per request;
* ``pool_sets``: distinct row sets per plan; a request carries one,
  drawn from the seed;
* ``max_coalesce``, ``max_queue``: the server's settings.

Every seed offers the same work: the N = rate x seconds arrivals are the
N exponential quantiles of the rate, in an order drawn from the seed, and
each tenant's request count is its Zipf share of N, in a seed-drawn
order. The generator and the server share one thread: it submits every
request that is due, pumps one coalesced group when the queue holds any,
and otherwise sleeps until the next due time. A request is timed from its
due time to the end of the pump that served it; one that is rejected, or
not served within a minute of the window's close, counts as missing and
takes that whole span as its latency. ``request_p95_ms`` is the 95th
percentile (linear interpolation) over all requests of the window.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from bench import inputs, reference
from bench.harness import Window
from bench.trace import telemetry_spans

GRACE_S = 60.0


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds): N = round(rate x seconds) exponential
    inter-arrival quantiles in a seed-drawn order."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds * (1.0 - 0.5 / n) / gaps.sum()
    rng = np.random.default_rng([seed, 1])
    return np.cumsum(rng.permutation(gaps))


def tenant_sequence(n: int, tenants: int, zipf_s: float,
                    seed: int) -> np.ndarray:
    """Each tenant's Zipf share of n requests (largest remainders), in a
    seed-drawn order."""
    w = 1.0 / (np.arange(tenants) + 1.0) ** zipf_s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    rng = np.random.default_rng([seed, 2])
    return rng.permutation(np.repeat(np.arange(tenants), counts))


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 trace: bool = False) -> None:
        self.cfg, self.traffic, self.seed, self.trace = (config, traffic,
                                                         seed, trace)
        self.families = list(config["serve_families"])
        self.T = int(traffic["tenants"])
        self.rate = float(traffic["rate_per_s"])
        self.bench_spans: List[Tuple[float, float, str]] = []

    def plan_of(self, t: int) -> int:
        return t % len(self.families)

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import repro.api as A
        from repro.core.graphs import Graph
        from repro.serve import SessionServer
        from repro.telemetry import TelemetrySpec
        tr = self.traffic
        self.g = inputs.build_graph(self.cfg)
        graph = Graph(self.g.p, tuple(self.g.edges))
        self.pools = []
        for k, fam in enumerate(self.families):
            _, sets = inputs.sample_sets(fam, self.g, self.seed, self.cfg,
                                         int(tr["pool_sets"]),
                                         int(tr["rows"]), stream=k)
            # requests arrive from clients: rows live on the host
            self.pools.append([np.asarray(x) for x in sets])
        spec = TelemetrySpec() if self.trace else None
        self.plans = [A.Plan(graph=graph, family=f, telemetry=spec,
                             precision=self.cfg["precision"])
                      for f in self.families]
        self.server = SessionServer(max_queue=int(tr["max_queue"]),
                                    max_coalesce=int(tr["max_coalesce"]))
        for t in range(self.T):
            self.server.register(f"t{t}", self.plans[self.plan_of(t)])
        self._warm_up()

    def _warm_up(self) -> None:
        """Serve every group size each plan can form (one request per
        tenant per group), twice."""
        for _ in range(2):
            for k in range(len(self.families)):
                mine = [t for t in range(self.T) if self.plan_of(t) == k]
                for r in range(1, len(mine) + 1):
                    for j, t in enumerate(mine[:r]):
                        self.server.submit(f"t{t}", self.pools[k][j])
                    self.server.drain()

    def schedule(self, rate: float, seconds: float) -> None:
        self.rate = rate
        self.due = arrivals(rate, seconds, self.seed)
        n = len(self.due)
        self.tenant = tenant_sequence(n, self.T, float(self.traffic["zipf_s"]),
                                      self.seed)
        rng = np.random.default_rng([self.seed, 3])
        self.set_idx = rng.integers(int(self.traffic["pool_sets"]), size=n)

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> Window:
        if getattr(self, "due", None) is None:
            self.schedule(self.rate, seconds)
        server, n = self.server, len(self.due)
        rec = server.recorder
        if self.trace:
            rec.event("bench_sync")
            self.offset = time.perf_counter() - rec.events[-1]["t"]
            self.mark = len(rec.events)
        submit = np.full(n, np.nan)
        start = np.full(n, np.nan)
        done = np.full(n, np.nan)
        size = np.zeros(n, dtype=np.int64)
        self.thetas: Dict[int, np.ndarray] = {}
        where: Dict[int, int] = {}
        rejected = 0
        t0 = time.perf_counter()
        due = t0 + self.due
        i = 0
        while True:
            now = time.perf_counter()
            while i < n and due[i] <= now:
                t = int(self.tenant[i])
                X = self.pools[self.plan_of(t)][int(self.set_idx[i])]
                ticket = server.submit(f"t{t}", X)
                submit[i] = time.perf_counter()
                if ticket.admitted:
                    where[id(ticket)] = i
                else:
                    rejected += 1
                i += 1
            if server.queue_depth:
                a = time.perf_counter()
                served = server.pump()
                b = time.perf_counter()
                self.bench_spans.append((a, b, "bench:pump"))
                for ticket in served:
                    j = where.pop(id(ticket))
                    start[j], done[j] = a, b
                    size[j] = ticket.result.coalesce_size
                    self.thetas[j] = np.asarray(ticket.result.theta,
                                                np.float64)
            elif i < n:
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    a = time.perf_counter()
                    time.sleep(wait)
                    self.bench_spans.append(
                        (a, time.perf_counter(), "bench:wait_for_arrival"))
            else:
                break
            if time.perf_counter() > t0 + seconds + GRACE_S:
                break
        ok = ~np.isnan(done)
        lat = np.where(ok, done - due, seconds + GRACE_S)
        last = np.nanmax(done) if ok.any() else t0 + seconds
        late = submit[~np.isnan(submit)] - due[~np.isnan(submit)]
        return Window(
            attempted=n, failed=int(n - ok.sum()),
            end_to_end={"request_p95_ms":
                        1e3 * float(np.percentile(lat, 95))},
            stats={"requests": n, "served": int(ok.sum()),
                   "rejected": rejected, "rate_per_s": self.rate,
                   "served_per_s": float(ok.sum() / (last - t0)),
                   "request_p50_ms": 1e3 * float(np.percentile(lat, 50)),
                   "generator_late_mean_ms": 1e3 * float(late.mean()),
                   "generator_late_max_ms": 1e3 * float(late.max()),
                   "coalesce_mean": float(size[ok].mean())
                   if ok.any() else 0.0},
            samples={"queue_wait_s": list(start[ok] - due[ok]),
                     "coalesce_sizes": list(size[ok])})

    def telemetry(self):
        return []

    def host_spans(self):
        spans = list(self.bench_spans)
        if self.trace:
            snap = self.server.recorder.snapshot(self.mark)
            spans += telemetry_spans([snap], self.offset)
        return spans

    def work(self) -> dict:
        return {"p": self.g.p, "m": self.g.m, "n": int(self.traffic["rows"]),
                "C": 1, "degrees": self.g.degrees()}

    # ------------------------------------------------------------ check
    def release(self) -> None:
        self.server = None
        self.plans = None

    def _key(self, j: int) -> Tuple[int, int]:
        return self.plan_of(int(self.tenant[j])), int(self.set_idx[j])

    def reference_theta(self, key, prec: str = "f64") -> np.ndarray:
        k, s = key
        p, edges = self.g.p, self.g.edges
        fits = reference.local_fits(self.families[k],
                                    np.asarray(self.pools[k][s], np.float64),
                                    p, edges, prec=prec)
        comb = reference.combine("diagonal", fits, p, edges)
        return np.array([comb[a] for a in range(p + len(edges))])

    def control_answers(self, prec: str) -> None:
        refs = {}
        for j in self.thetas:
            key = self._key(j)
            if key not in refs:
                refs[key] = self.reference_theta(key, prec)
            self.thetas[j] = refs[key]

    def check(self) -> dict:
        """The worst gap over every served request."""
        refs: Dict[Tuple[int, int], np.ndarray] = {}
        got: Dict[str, float] = {}
        for j, theta in self.thetas.items():
            key = self._key(j)
            if key not in refs:
                refs[key] = self.reference_theta(key)
            for k, v in reference.gap_numbers(
                    "served_theta", np.abs(theta - refs[key])).items():
                got[k] = max(got.get(k, 0.0), v)
        return got
