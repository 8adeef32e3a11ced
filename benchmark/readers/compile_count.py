"""Backend compiles JAX made inside the measured window (jax.monitoring
events; a persistent-cache read counts, it stalls the host too). Must be
0: anything else compiled or was loaded inside the window."""


def read(ev, params):
    return ev.compiles_in_window
