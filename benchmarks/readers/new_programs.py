"""Programs compiled or loaded inside the window: the larger of what JAX
reported for the process and what the engine counts of its own executables
(``compiled_executables()``).  Expected 0; anything else also makes the run
incorrect."""


def read(ctx):
    return max(ctx.run.compiles_in_window,
               ctx.run.values.get("engine_new_executables", 0))
