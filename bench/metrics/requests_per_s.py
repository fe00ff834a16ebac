"""Chain-terminal requests completed inside the window, per second of it."""


def read(run):
    w = run.window
    return len(w.completed) / (w.t_close - w.t_open)
