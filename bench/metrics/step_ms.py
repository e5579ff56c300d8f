"""Window over completed ``run()`` calls: one call is one whole forward
step of the compiled plan, host transfers included."""


def read(run):
    return run.window_s / len(run.step_s) * 1e3
