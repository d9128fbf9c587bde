"""Executables compiled, or loaded from the persistent cache, inside
the measured window, from JAX's monitoring events.  Set-up serves every
pool round once, so it should read 0."""


def read(obs):
    return obs.window_compiles
