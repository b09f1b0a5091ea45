"""Programs compiled or loaded from the compile cache inside the window
(``jax.monitoring`` backend-compile events): 0 when set-up warmed every
program the window runs."""


def read(rec):
    return rec["compiles_in_window"]
