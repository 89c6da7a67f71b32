"""Scheduler: tokens the clients read in the window / decode steps taken
in it (counter differences): live rows per decode step."""


def read(obs):
    steps = obs.decode_steps()
    if not steps:
        return None
    return obs.tokens_in_window() / steps
