"""Model programs: seconds of the traced stretch / decode steps taken in
it (counter differences at its two ends), ms a step. Prefill chunks run
inside the same stretch: this is the wall a step costs, not its kernel
time."""


def read(obs):
    steps = obs.decode_steps(stretch=True)
    if not steps or not obs.stretch_s:
        return None
    return obs.stretch_s * 1e3 / steps
