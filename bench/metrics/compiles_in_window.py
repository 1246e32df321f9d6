"""Programs compiled or fetched from the compile cache while the window
ran (a ``jax.monitoring`` listener on the backend-compile event)."""


def read(r):
    return float(r.compiles)
