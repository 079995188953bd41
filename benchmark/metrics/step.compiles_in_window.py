"""How many of the window's `executor.train_step` spans built a program
(`compiled`): 0, or a step in the window was a compile and not a step.
Program span."""
from lib import spans


def read(run):
    steps = spans.window_compiles(run)
    return None if steps is None else len(steps)
