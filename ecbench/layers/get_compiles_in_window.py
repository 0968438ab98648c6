"""XLA compilations inside the measured window of a GET cell
(jax.monitoring): 0 when the warm-up met every shape and the compile
cache held."""


def read(obs, cell):
    return float(obs.counters["compiles_in_window"])
