#!/usr/bin/env python3
"""Drive the estimation path once on a TPU and check it against the host CPU.

    python3 chip_smoke.py [--seed 0]              # one chip: every phase
    python3 chip_smoke.py --chips 4 [--seed 0]    # four chips: sharded fit

One process holds the chip and goes through the entry points a user calls
(``Plan`` -> ``EstimationSession`` verbs, ``SessionServer``) at the
deployment sizes of Liu & Ihler (2012), Fig. 4: the 100-node scale-free
and Euclidean sensor graphs with n = 4000 samples, plus a 32x32 Ising
lattice at n = 4096. Parameters are drawn from ``--seed`` and samples by
chromatic Gibbs, both on the device.

Phases (one chip): Ising ``fit`` with five combiners and ``joint`` on both
fleet graphs, ``select`` and three ``simulate`` rounds on the scale-free
graph, a ``SessionServer`` answering 8 coalesced ``fit`` requests of 4
tenants on 2 plans (Ising and Gaussian on the scale-free graph), and
Gaussian, Potts (q = 3: two channels) and lattice ``fit``. With
``--chips 4`` only the Euclidean fleet ``fit`` runs, on a 4-device
``data`` mesh against ``mesh=None`` on one device.

Each phase prints one JSON line: ``wall_s`` / ``compile_s`` of the cold
call (bring-up observations, not metrics), ``warm_compiles`` (XLA
compilations during a warm repeat on fresh same-shape data; must be 0),
``warm_wall_s``, ``max_abs_diff`` (max |theta - theta_ref| against the
same call run on the host CPU backend in this process) and ``mse``
against the planted theta. The difference must stay within 1e-5, the
float32 plan tolerance, or, where the same call at float64 on the host
shows float32 itself to be further than that from the answer (the
ill-conditioned combiners), within three times the host float32 error
(``vs_cpu`` lists each). Every plan runs under a TelemetrySpec, and each
fit asserts that the bucket-Newton and score kernels were traced on the
``mosaic`` path.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits 2 and prints no such line; a failing phase
raises, and the script exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402

import repro.api as A  # noqa: E402
import repro.core as C  # noqa: E402
from repro.core.families import GAUSSIAN, POTTS3  # noqa: E402
from repro.serve import SessionServer  # noqa: E402
from repro.stream import ArrivalSpec  # noqa: E402
from repro.structure import StructureSpec  # noqa: E402
from repro.telemetry import TelemetrySpec  # noqa: E402

TOL = 1e-5                       # float32 plan tolerance (README)
ERR_FACTOR = 3                   # x the host float32 run's own error
P_FLEET, N_FLEET = 100, 4000     # Liu & Ihler (2012), Fig. 4, full mode
LATTICE, N_LATTICE = 32, 4096
GIBBS = dict(burnin=150, thin=2)
COMBINERS = ("uniform", "diagonal", "optimal", "max", "weighted_vote")
SERVE_ROWS = 1000
KERNEL_PATH = "mosaic"           # the dispatch path a TPU must resolve to
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA backend compilations of this process (any device)."""

    def __init__(self) -> None:
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            self.n += 1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def on_cpu():
    """Run what follows on the host CPU backend (the reference)."""
    return jax.default_device(jax.devices("cpu")[0])


@contextlib.contextmanager
def float64_on_cpu():
    """Run what follows at float64 on the host CPU: the stand-in for exact
    arithmetic that measures how far float32 itself lands from the answer."""
    with jax.enable_x64(True), on_cpu():
        yield


def f64(plan):
    return plan.replace(precision="float64")


def max_diff(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    check(a.shape == b.shape, f"shape {a.shape} != reference {b.shape}")
    return float(np.max(np.abs(a - b))) if a.size else 0.0


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def against_cpu(got, ref, exact=None):
    """Hold each TPU result ``got[key]`` to the same call on the host CPU.

    ``ref`` is that call at float32 and ``exact``, where given, at
    float64. The tolerance is TOL, or ERR_FACTOR times the host float32
    result's own error against float64 where that is larger: an
    ill-conditioned combiner can put float32 further than TOL from the
    answer on any backend, and two float32 backends then differ by up to
    the sum of their errors. A TPU result at most twice as far from the
    float64 answer as the host's stays within three times the host's
    error of it. Returns ``{key: {diff, tol[, err_f64, cpu_err_f64]}}``.
    """
    out = {}
    for key in ref:
        d = {"diff": max_diff(got[key], ref[key]), "tol": TOL}
        if exact is not None:
            d["err_f64"] = max_diff(got[key], exact[key])
            d["cpu_err_f64"] = max_diff(ref[key], exact[key])
            d["tol"] = max(TOL, ERR_FACTOR * d["cpu_err_f64"])
        out[str(key)] = d
    return out


def check_against_cpu(phase: str, diffs) -> None:
    bad = {k: v for k, v in diffs.items() if not v["diff"] <= v["tol"]}
    check(not bad, f"{phase}: |theta - theta_cpu| over tolerance: {bad}")


def check_kernel_paths(snapshot, phase: str) -> None:
    """Every traced bucket-Newton and score kernel ran on KERNEL_PATH."""
    seen = {}
    for ev in snapshot.events:
        name = ev["name"]
        if ev["kind"] == "event" and (name == "kernel.bucket_newton_stats"
                                      or name.startswith("kernel.score")):
            seen.setdefault(name, set()).add(ev["tags"]["backend"])
    check("kernel.bucket_newton_stats" in seen,
          f"{phase}: no bucket-Newton kernel traced")
    check(any(n.startswith("kernel.score") for n in seen),
          f"{phase}: no score kernel traced")
    check(all(paths == {KERNEL_PATH} for paths in seen.values()),
          f"{phase}: kernel paths {seen}, expected {KERNEL_PATH!r}")


def warm_repeat(counter: CompileCounter, fn):
    """(compilations, wall seconds) of one warm call of ``fn``."""
    c0, t0 = counter.n, time.perf_counter()
    fn()
    return counter.n - c0, time.perf_counter() - t0


# ------------------------------------------------------------------ data
def fleet_graphs():
    return {"scalefree": C.scale_free_graph(P_FLEET, m=1, seed=0),
            "euclidean": C.euclidean_graph(P_FLEET, radius=0.15, seed=0)}


def ising_data(g, n, key):
    k_model, k_draw = jax.random.split(key)
    model = C.random_model(g, 0.5, 0.5, k_model)
    X = C.gibbs_sample(model, n, k_draw, method="chromatic", **GIBBS)
    return np.asarray(model.theta), X


def family_data(family, g, n, key):
    k_model, k_draw = jax.random.split(key)
    theta = family.random_params(g, k_model)
    X = family.sample(g, theta, n, k_draw, **GIBBS)
    return np.asarray(theta), X


def plan_for(g, family="ising", combiners=("diagonal",), **kw):
    return A.Plan(graph=g, family=family, combiners=combiners,
                  telemetry=TelemetrySpec(), **kw)


# ---------------------------------------------------------------- phases
def fit_phase(phase, plan, X, theta_star, counter):
    sess = plan.session()
    res = sess.fit(X)
    check_kernel_paths(res.telemetry, phase)
    X_fresh = X[::-1]
    warm, warm_s = warm_repeat(counter, lambda: sess.fit(X_fresh))
    with on_cpu():
        ref = sess.fit(np.asarray(X))
    with float64_on_cpu():
        exact = f64(plan).session().fit(np.asarray(X, np.float64))
    diffs = against_cpu(res.combined, ref.combined, exact.combined)
    report(phase, wall_s=res.wall_s, compile_s=res.compile_s,
           new_compiles=res.new_compiles, warm_compiles=warm,
           warm_wall_s=warm_s,
           max_abs_diff=max(d["diff"] for d in diffs.values()),
           vs_cpu=diffs,
           mse={c: C.mse(res.combined[c], theta_star, sess.free)
                for c in plan.combiners},
           score_norm=res.score_norm)
    check(warm == 0, f"{phase}: {warm} compilations on a warm repeat")
    check_against_cpu(phase, diffs)


def joint_phase(phase, plan, X, theta_star, counter):
    sess = plan.session()
    res = sess.joint(X)
    X_fresh = X[::-1]
    warm, warm_s = warm_repeat(counter, lambda: sess.joint(X_fresh))
    with on_cpu():
        ref = sess.joint(np.asarray(X))
    with float64_on_cpu():
        exact = f64(plan).session().joint(np.asarray(X, np.float64))
    diffs = against_cpu({"joint": res.theta}, {"joint": ref.theta},
                        {"joint": exact.theta})
    report(phase, wall_s=res.wall_s, compile_s=res.compile_s,
           warm_compiles=warm, warm_wall_s=warm_s,
           max_abs_diff=diffs["joint"]["diff"], vs_cpu=diffs,
           mse=C.mse(res.theta, theta_star, sess.free))
    check(warm == 0, f"{phase}: {warm} compilations on a warm repeat")
    check_against_cpu(phase, diffs)


def select_phase(phase, plan, X, g_true, counter):
    sess = plan.session()
    spec = StructureSpec(policy="knn", knn_k=8)
    res = sess.select(X, spec)
    # the candidate graph is data-dependent: warm-repeat on the same data
    warm, warm_s = warm_repeat(counter, lambda: sess.select(X, spec))
    with on_cpu():
        ref = sess.select(np.asarray(X), spec)
    check(res.support == ref.support, f"{phase}: support differs from CPU")
    diffs = against_cpu(dict(enumerate(res.thetas)),
                        dict(enumerate(ref.thetas)))
    report(phase, wall_s=res.wall_s, compile_s=res.compile_s,
           warm_compiles=warm, warm_wall_s=warm_s,
           max_abs_diff=max(d["diff"] for d in diffs.values()),
           edges=res.edge_metrics(g_true.edges))
    check(warm == 0, f"{phase}: {warm} compilations on a warm repeat")
    check_against_cpu(phase, diffs)


def simulate_phase(phase, plan, X, theta_star, seed, counter):
    sess = plan.session()
    pool = np.asarray(X)

    def run():
        sim = sess.simulate(pool, theta_star=theta_star, seed=seed,
                            arrivals=ArrivalSpec(rate=400.0))
        return sim.run(3)

    t0 = time.perf_counter()
    res = run()
    wall = time.perf_counter() - t0
    warm, warm_s = warm_repeat(counter, run)
    with on_cpu():
        ref = run()
    diffs = against_cpu({"final": res.theta[-1]}, {"final": ref.theta[-1]})
    report(phase, wall_s=wall, warm_compiles=warm, warm_wall_s=warm_s,
           max_abs_diff=diffs["final"]["diff"], rounds=int(res.rounds[-1]),
           err=float(res.err[-1]))
    check(warm == 0, f"{phase}: {warm} compilations on a warm repeat")
    check_against_cpu(phase, diffs)


def serve_phase(phase, plans, pools, theta_stars, counter):
    """4 tenants, 2 per plan; each tenant sends 2 requests of its own rows
    per round (the warm round sends the same rows in reverse order)."""
    tenants = [(f"t{i}", i // 2, pools[i // 2], i % 2) for i in range(4)]

    def serve_round(server, rnd):
        tickets = []
        for rep in range(2):
            for tid, _, pool, slot in tenants:
                lo = (2 * slot + rep) * SERVE_ROWS
                rows = pool[lo:lo + SERVE_ROWS]
                tickets.append(server.submit(tid, rows[::-1] if rnd
                                             else rows))
        server.drain()
        check(all(t.done for t in tickets), f"{phase}: request not served")
        return tickets

    def new_server(plans):
        server = SessionServer(max_coalesce=8)
        for tid, which, _, _ in tenants:
            server.register(tid, plans[which])
        return server

    server = new_server(plans)
    t0 = time.perf_counter()
    tickets = serve_round(server, 0)
    wall = time.perf_counter() - t0
    sizes = sorted({t.result.coalesce_size for t in tickets})
    warm, warm_s = warm_repeat(counter, lambda: serve_round(server, 1))
    with on_cpu():
        ref = serve_round(new_server(plans), 0)
    with float64_on_cpu():
        exact = serve_round(new_server([f64(p) for p in plans]), 0)

    def thetas(ts):
        return {i: t.result.theta for i, t in enumerate(ts)}
    diffs = against_cpu(thetas(tickets), thetas(ref), thetas(exact))
    free = [plan.session().free for plan in plans]
    report(phase, wall_s=wall, requests=len(tickets), coalesce_sizes=sizes,
           warm_compiles=warm, warm_wall_s=warm_s,
           max_abs_diff=max(d["diff"] for d in diffs.values()),
           vs_cpu=diffs,
           mse=[C.mse(t.result.theta, theta_stars[which], free[which])
                for t, (_, which, _, _) in zip(tickets, tenants * 2)])
    check(sizes == [2], f"{phase}: coalesce sizes {sizes}, expected [2]")
    check(warm == 0, f"{phase}: {warm} compilations on a warm repeat")
    check_against_cpu(phase, diffs)


def sharded_fit_phase(phase, plan, X, counter, n_devices):
    """The data-mesh fit against mesh=None on one device."""
    sess = plan.replace(mesh="data").session()
    res = sess.fit(X)
    check_kernel_paths(res.telemetry, phase)
    rows = {}
    for ev in res.telemetry.events:
        if ev["name"] == "engine.shard_rows":
            tags = ev["tags"]
            rows.setdefault(tags["deg_pad"], {})[tags["device"]] = ev["value"]
    check(len(rows) == sess.n_buckets, f"{phase}: shard rows {rows}")
    # each device holds one contiguous, equal slice of every bucket's
    # (zero-padded) node axis: ceil(k / devices) rows
    for b in sess.buckets:
        share = -(-len(b.nodes) // n_devices)
        per_device = rows[b.deg_pad]
        check(sorted(per_device) == list(range(n_devices))
              and set(per_device.values()) == {share},
              f"{phase}: bucket deg_pad={b.deg_pad} (k={len(b.nodes)}) "
              f"rows per device {per_device}, expected {share} each")
    X_fresh = X[::-1]
    warm, warm_s = warm_repeat(counter, lambda: sess.fit(X_fresh))
    single = plan.session().fit(X)
    diff = max(max_diff(res.combined[c], single.combined[c])
               for c in plan.combiners)
    report(phase, wall_s=res.wall_s, compile_s=res.compile_s,
           warm_compiles=warm, warm_wall_s=warm_s,
           max_abs_diff_vs_one_device=diff,
           bucket_nodes={str(b.deg_pad): len(b.nodes) for b in sess.buckets},
           shard_rows={str(k): v for k, v in sorted(rows.items())})
    check(warm == 0, f"{phase}: {warm} compilations on a warm repeat")
    check(diff <= TOL, f"{phase}: max |theta_mesh - theta_one| = {diff}")


# ------------------------------------------------------------------ main
def run_one_chip(seed: int, counter: CompileCounter) -> None:
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    t0 = time.perf_counter()
    graphs = fleet_graphs()
    data = {name: ising_data(g, N_FLEET, k)
            for (name, g), k in zip(graphs.items(), keys[:2])}
    g_sf = graphs["scalefree"]
    gauss = family_data(GAUSSIAN, g_sf, N_FLEET, keys[2])
    potts = family_data(POTTS3, g_sf, N_FLEET, keys[3])
    g_lat = C.grid_graph(LATTICE, LATTICE)
    lattice = ising_data(g_lat, N_LATTICE, keys[4])
    jax.block_until_ready([d[1] for d in (*data.values(), gauss, potts,
                                          lattice)])
    report("data", wall_s=time.perf_counter() - t0)

    for name, g in graphs.items():
        theta, X = data[name]
        plan = plan_for(g, combiners=COMBINERS)
        fit_phase(f"fit_ising_{name}", plan, X, theta, counter)
        joint_phase(f"joint_ising_{name}", plan, X, theta, counter)
    theta, X = data["scalefree"]
    plan = plan_for(g_sf)
    select_phase("select_ising_scalefree", plan, X, g_sf, counter)
    simulate_phase("simulate_ising_scalefree", plan, X, theta, seed,
                   counter)
    # 1000-row requests on the Euclidean graph saturate some local fits
    # (|theta| > 10, no finite optimum): the server's two plans are Ising
    # and Gaussian on the scale-free graph
    serve_phase("serve_fleet_scalefree",
                [A.Plan(graph=g_sf), A.Plan(graph=g_sf, family="gaussian")],
                [np.asarray(X), np.asarray(gauss[1])], [theta, gauss[0]],
                counter)
    fit_phase("fit_gaussian_scalefree", plan_for(g_sf, "gaussian"),
              gauss[1], gauss[0], counter)
    fit_phase("fit_potts3_scalefree", plan_for(g_sf, "potts"),
              potts[1], potts[0], counter)
    fit_phase("fit_ising_lattice32", plan_for(g_lat), lattice[1],
              lattice[0], counter)


def run_four_chips(seed: int, counter: CompileCounter) -> None:
    n_devices = len(jax.devices())
    check(n_devices == 4, f"--chips 4 needs 4 devices, found {n_devices}")
    g = fleet_graphs()["euclidean"]
    _, X = ising_data(g, N_FLEET, jax.random.split(
        jax.random.PRNGKey(seed), 5)[1])
    sharded_fit_phase("fit_ising_euclidean_data_mesh",
                      plan_for(g, combiners=COMBINERS), X, counter,
                      n_devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2
    counter = CompileCounter()
    if args.chips == 4:
        run_four_chips(args.seed, counter)
    else:
        run_one_chip(args.seed, counter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
