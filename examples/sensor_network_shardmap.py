"""Faithful sensor-network execution: one DEVICE per SENSOR via shard_map.

Each of the 8 host devices plays one sensor of an 8-node star-graph Ising
model: it sees only its local data X_A(i), fits its conditional-likelihood
estimator with a fixed-iteration Newton solve (pure lax — SPMD-safe), and
the consensus happens through jax.lax collectives (all_gather of the
per-sensor estimates + weights). This is the paper's Sec. 3 *system*, not
just its math: data never leaves the sensor; only O(deg) scalars do.

    python examples/sensor_network_shardmap.py     (sets its own XLA_FLAGS)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import PartitionSpec as P   # noqa: E402

import repro.core as C          # noqa: E402

P_NODES = 8
N = 2000


def main():
    g = C.star_graph(P_NODES)
    model = C.random_model(g, 0.5, 0.5, jax.random.PRNGKey(0))
    theta_star = np.asarray(model.theta)
    X = C.exact_sample(model, N, jax.random.PRNGKey(1))

    # --- per-sensor views, padded to max degree for SPMD uniformity -------
    dmax = max(g.degree(i) for i in range(g.p))
    Z = np.zeros((g.p, N, dmax), np.float32)       # neighbor designs
    M = np.zeros((g.p, dmax), np.float32)          # valid-coordinate mask
    xi = np.zeros((g.p, N), np.float32)
    for i in range(g.p):
        nb = [g.edges[k][0] if g.edges[k][1] == i else g.edges[k][1]
              for k in g.incident_edges(i)]
        Z[i, :, : len(nb)] = np.asarray(X)[:, nb]
        M[i, : len(nb)] = 1.0
        xi[i] = np.asarray(X)[:, i]

    mesh = jax.make_mesh((P_NODES,), ("sensor",))

    def sensor_program(Z, xi, mask):
        """Runs ON each sensor device; sees only that sensor's shard."""
        Z, xi, mask = Z[0], xi[0], mask[0]          # local block

        def nll_grad_hess(w):
            eta = Z @ (w[1:] * mask) + w[0]
            r = 2.0 * xi * jax.nn.sigmoid(-2.0 * xi * eta)
            zb = jnp.concatenate([jnp.ones((N, 1)), Z * mask], 1)
            gvec = (r[:, None] * zb).mean(0)
            s = 4.0 * jax.nn.sigmoid(2.0 * xi * eta) * \
                jax.nn.sigmoid(-2.0 * xi * eta)
            H = (zb * s[:, None]).T @ zb / N + \
                1e-4 * jnp.eye(1 + Z.shape[1])      # ridge keeps padding sane
            return gvec, H, zb

        w = jnp.zeros(1 + Z.shape[1])
        for _ in range(25):                          # fixed-iteration Newton
            gvec, H, zb = nll_grad_hess(w)
            w = w + jnp.linalg.solve(H, gvec)
        # local inverse-variance weights (Prop 4.4: no extra communication)
        gvec, H, zb = nll_grad_hess(w)
        eta = Z @ (w[1:] * mask) + w[0]
        r = 2.0 * xi * jax.nn.sigmoid(-2.0 * xi * eta)
        G = r[:, None] * zb
        J = G.T @ G / N
        Hinv = jnp.linalg.inv(H)
        V = Hinv @ J @ Hinv
        wts = 1.0 / jnp.maximum(jnp.diag(V)[1:], 1e-9) * mask
        # the ONLY communication: per-sensor (estimate, weight) vectors
        all_est = jax.lax.all_gather(w[1:] * mask, "sensor")   # (p, dmax)
        all_wts = jax.lax.all_gather(wts, "sensor")            # (p, dmax)
        return all_est[None], all_wts[None]

    fn = jax.shard_map(sensor_program, mesh=mesh,
                       in_specs=(P("sensor"), P("sensor"), P("sensor")),
                       out_specs=(P("sensor"), P("sensor")),
                       check_vma=False)
    est, wts = jax.jit(fn)(jnp.asarray(Z), jnp.asarray(xi), jnp.asarray(M))
    est, wts = np.asarray(est)[0], np.asarray(wts)[0]

    # --- per-edge consensus (every sensor can do this locally) ------------
    theta_max = np.zeros(g.n_params)
    theta_lin = np.zeros(g.n_params)
    for k, (i, j) in enumerate(g.edges):
        pos_i = g.incident_edges(i).index(k)
        pos_j = g.incident_edges(j).index(k)
        cand = np.array([est[i, pos_i], est[j, pos_j]])
        ww = np.array([wts[i, pos_i], wts[j, pos_j]])
        theta_max[g.p + k] = cand[np.argmax(ww)]
        theta_lin[g.p + k] = (ww * cand).sum() / ww.sum()

    free = C.free_indices(g, include_singleton=False)
    print(f"devices = {jax.device_count()} (one per sensor)")
    print(f"max-consensus    MSE: {C.mse(theta_max, theta_star, free):.5f}")
    print(f"linear-diagonal  MSE: {C.mse(theta_lin, theta_star, free):.5f}")
    # centralized reference
    fits = C.fit_all_local(g, X, include_singleton=False,
                           theta_fixed=jnp.asarray(theta_star))
    ref = C.combine(g, fits, "max", include_singleton=False,
                    theta_fixed=theta_star)
    print(f"centralized max  MSE: {C.mse(ref, theta_star, free):.5f} "
          f"(should be close)")


if __name__ == "__main__":
    main()
