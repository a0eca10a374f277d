"""Backend compile requests (cache hits included) inside the measured
window: should be 0. Source: jax.monitoring."""


def read(run):
    return float(sum(1 for t, _n, _s in run.compiles
                     if run.t_a <= t < run.t_b))
