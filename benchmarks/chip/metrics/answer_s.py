"""Time from the window's start to the last answer, over the answers: the
mean time to a certified answer for one client who waits for each."""


def read(run):
    return run.window_s / len(run.answers) if run.answers else None
