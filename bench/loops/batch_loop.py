"""Closed loop of back-to-back session verb calls (``fit`` or ``joint``).

Traffic parameters (``bench/traffic/<mix>.json``):

* ``verb``: the ``EstimationSession`` verb each call drives;
* ``sets``: distinct sample sets drawn in set-up; call k uses set k mod
  ``sets``, so no two consecutive calls see the same rows;
* ``warmup_calls``: calls made in set-up, on sets 0, 1, ...;
* ``check_sets``: how many of the sets the window used are compared with
  the reference (drawn from the seed); every call on them is compared;
* ``check_nodes``: null, or a number at least p, compares every node;
  a smaller number draws that many nodes from the seed, and compares
  them and their neighbours, and every parameter all of whose owners are
  among them.

The configuration gives the graph, family, n and combiners. ``estimate_ms``
is the window over the number of calls completed in it.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from bench import inputs, reference
from bench.harness import Window
from bench.trace import telemetry_spans


def call_stats(ends, sets: int) -> dict:
    """Spread of single calls in a window (stderr only): quartiles and
    extremes of the call times, and the mean call time on each set."""
    ms = 1e3 * np.diff(np.concatenate([[0.0], ends]))
    q = np.percentile(ms, [0, 25, 50, 75, 100])
    per_set = [float(np.mean(ms[k::sets])) for k in range(min(sets, len(ms)))]
    return {"call_ms_min_q1_med_q3_max": [round(float(v), 3) for v in q],
            "call_ms_by_set": [round(v, 3) for v in per_set]}


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 trace: bool = False) -> None:
        self.cfg, self.traffic, self.seed, self.trace = (config, traffic,
                                                         seed, trace)
        self.verb = traffic["verb"]
        self.family = config["family"]
        self.answers: Dict[int, List[dict]] = {}
        self.snapshots: list = []
        self.calls = 0

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import repro.api as A
        from repro.core.graphs import Graph
        from repro.telemetry import TelemetrySpec
        cfg = self.cfg
        self.g = inputs.build_graph(cfg)
        _, self.sets = inputs.sample_sets(
            self.family, self.g, self.seed, cfg, int(self.traffic["sets"]),
            int(cfg["n"]))
        jax.block_until_ready(self.sets)
        self.plan = A.Plan(
            graph=Graph(self.g.p, tuple(self.g.edges)), family=self.family,
            combiners=tuple(cfg["combiners"]),
            admm_iters=int(cfg.get("admm_iters", 30)),
            precision=cfg["precision"],
            telemetry=TelemetrySpec() if self.trace else None)
        self.session = self.plan.session()
        for k in range(int(self.traffic["warmup_calls"])):
            getattr(self.session, self.verb)(self.sets[k % len(self.sets)])

    # ------------------------------------------------------------ window
    def _record(self, s: int, res) -> None:
        ans = {"theta": np.asarray(res.theta, np.float64),
               "score_norm": float(res.score_norm)}
        if self.verb == "fit":
            ans["local"] = {f.i: np.asarray(f.theta) for f in res.fits}
            ans["combined"] = {c: np.asarray(v, np.float64)
                               for c, v in res.combined.items()}
        self.answers.setdefault(s, []).append(ans)

    def window(self, seconds: float) -> Window:
        call = getattr(self.session, self.verb)
        rec = self.session.recorder
        if self.trace:
            rec.event("bench_sync")
            self.offset = time.perf_counter() - rec.events[-1]["t"]
        S = len(self.sets)
        ends = []
        t0 = time.perf_counter()
        while True:
            s = self.calls % S
            with jax.profiler.TraceAnnotation("bench_call"):
                res = call(self.sets[s])
            self._record(s, res)
            if res.telemetry is not None:
                self.snapshots.append(res.telemetry)
            self.calls += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
        return Window(attempted=self.calls, failed=0,
                      end_to_end={"estimate_ms": 1e3 * elapsed / self.calls},
                      stats={"calls": self.calls, "window_s": elapsed,
                             **call_stats(ends, S)})

    def telemetry(self):
        return self.snapshots

    def host_spans(self):
        if not self.trace:
            return []
        return telemetry_spans(self.snapshots, self.offset)

    def work(self) -> dict:
        return {"p": self.g.p, "m": self.g.m, "n": int(self.cfg["n"]),
                "C": 1, "degrees": self.g.degrees(), "calls": self.calls,
                "verb": self.verb,
                "want_influence": "optimal" in self.cfg["combiners"]}

    # ------------------------------------------------------------ check
    def release(self) -> None:
        """Keep the checked sets on the host; free the program's state."""
        rng = np.random.default_rng([self.seed, 7])
        used = sorted(self.answers)
        n_check = min(int(self.traffic["check_sets"]), len(used))
        self.checked = sorted(int(s) for s in
                              rng.choice(used, size=n_check, replace=False))
        self.host_sets = {s: np.asarray(self.sets[s], np.float64)
                          for s in self.checked}
        self.answers = {s: self.answers[s] for s in self.checked}
        self.sets = None
        self.session = None

    def _nodes(self):
        """Nodes and parameters compared, drawn from the seed."""
        k = self.traffic.get("check_nodes")
        p, edges = self.g.p, self.g.edges
        if k is None or int(k) >= p:
            return list(range(p)), None
        rng = np.random.default_rng([self.seed, 11])
        seeds = rng.choice(p, size=int(k), replace=False)
        nb = self.g.neighbors()
        nodes = set(int(i) for i in seeds)
        for i in seeds:
            nodes.update(int(j) for j in nb[int(i)])
        params = sorted(nodes) + [p + e for e, (a, b) in enumerate(edges)
                                  if a in nodes and b in nodes]
        return sorted(nodes), params

    def reference_answers(self, s: int, prec: str = "f64", nodes=None,
                          params=None) -> dict:
        """What the reference computes for set s, at ``prec``."""
        X, p, edges = self.host_sets[s], self.g.p, self.g.edges
        if self.verb == "joint":
            return {"theta": reference.admm_joint(
                self.family, X, p, edges, int(self.cfg.get("admm_iters", 30)),
                prec=prec)}
        fits = reference.local_fits(
            self.family, X, p, edges, nodes=nodes, prec=prec,
            want_influence="optimal" in self.cfg["combiners"])
        return {"fits": fits,
                "combined": {c: reference.combine(c, fits, p, edges, params)
                             for c in self.cfg["combiners"]}}

    def control_answers(self, prec: str) -> None:
        """Put the reference at ``prec`` in the program's place: its
        estimates, and its score norm at the program's headline estimate."""
        nodes, params = self._nodes()
        p, edges = self.g.p, self.g.edges
        for s in self.checked:
            ref = self.reference_answers(s, prec, nodes, params)
            theta = self.answers[s][0]["theta"]
            ans = {"theta": theta, "score_norm": float(np.linalg.norm(
                reference.pseudo_score(self.family, theta, self.host_sets[s],
                                       p, edges, prec)))}
            if self.verb == "joint":
                ans["theta_joint"] = ref["theta"]
            else:
                ans["local"] = {i: f["theta"] for i, f in ref["fits"].items()}
                ans["combined"] = {
                    c: {a: (v[0] if isinstance(v, list) else v)
                        for a, v in vals.items()}
                    for c, vals in ref["combined"].items()}
            self.answers[s] = [ans]

    def check(self) -> dict:
        """Every compared number, the worst over the checked answers."""
        nodes, params = self._nodes()
        p, edges = self.g.p, self.g.edges
        got: Dict[str, float] = {}

        def worst(numbers):
            for k, v in numbers.items():
                got[k] = max(got.get(k, 0.0), v)

        for s in self.checked:
            ref = self.reference_answers(s, "f64", nodes, params)
            X = self.host_sets[s]
            seen = {}
            for ans in self.answers[s]:
                if self.verb == "joint":
                    worst(reference.gap_numbers("joint_theta", np.abs(
                        ans.get("theta_joint", ans["theta"])
                        - ref["theta"])))
                else:
                    worst(reference.gap_numbers(
                        "local_theta",
                        reference.local_gaps(ans["local"], ref["fits"],
                                             nodes)))
                    for c, vals in ref["combined"].items():
                        worst(reference.gap_numbers(
                            "combined_theta", reference.combined_gaps(
                                ans["combined"][c], vals)))
                key = ans["theta"].tobytes()
                if key not in seen:
                    g = reference.pseudo_score(self.family, ans["theta"], X,
                                               p, edges)
                    seen[key] = float(np.linalg.norm(g))
                gap = abs(ans["score_norm"] - seen[key])
                worst({"score_norm_gap": gap if np.isfinite(gap)
                       else np.inf})
        return got
