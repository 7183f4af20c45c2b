"""Quickstart: DSML (paper Algorithm 1) vs local lasso / group lasso on
synthetic shared-support multi-task regression.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import (
    dsml_fit, estimation_error, gen_regression, group_lasso, hamming,
    prediction_error, support_of,
)


def main():
    key = jax.random.PRNGKey(0)
    m, n, p, s = 10, 100, 200, 10
    print(f"tasks m={m}, samples/task n={n}, dims p={p}, support s={s}")
    data = gen_regression(key, m=m, n=n, p=p, s=s, signal_low=0.3)

    base = float(jnp.sqrt(jnp.log(float(p)) / n))
    res = dsml_fit(data.Xs, data.ys, lam=4 * base, mu=base, Lam=1.0)

    def report(name, B_hat):
        print(f"{name:12s} hamming={int(hamming(support_of(B_hat, 1e-3), data.support)):3d}  "
              f"est_err={float(estimation_error(B_hat, data.B)):7.2f}  "
              f"pred_err={float(prediction_error(B_hat, data.B, data.Sigma)):7.4f}")

    report("local lasso", res.beta_local.T)
    report("group lasso", group_lasso(data.Xs, data.ys, 0.3))
    report("DSML", res.beta_tilde.T)
    print(f"\nDSML support correct: {bool(jnp.all(res.support == data.support))}")
    print(f"communication: {m} x {p} floats up, {p} bits down "
          f"(vs {m}x{n}x{p} floats to centralize)")


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
