"""Images finished by the window's close over the window's seconds."""


def read(run):
    return run.window.done_in_window / run.seconds
