"""Model programs: device seconds of the prefill programs
(``jit_prefill_*`` in the trace's ``XLA Modules`` line: the admit and
chunk programs) / device seconds of all the programs the reduction
lists (the ten that took most time), %. None where no listed program
carries the scheduler's kind prefixes (a program that names them by
position)."""

KINDS = ("jit_prefill_", "jit_decode_", "jit_spec_", "jit_kv_")


def read(obs):
    modules = obs.trace.get("modules") or []
    total = sum(seconds for _, seconds, _ in modules)
    if not total or not any(name.startswith(KINDS) for name, _, _ in modules):
        return None
    prefill = sum(seconds for name, seconds, _ in modules
                  if name.startswith("jit_prefill_"))
    return 100.0 * prefill / total
