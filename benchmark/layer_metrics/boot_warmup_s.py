"""Launcher and engine: the gauge ``serve_boot_warmup_seconds`` at the
window's first scrape, s: ``warmup()`` entry until its last job has run
on the scheduler thread (``serve_boot_programs_total`` jobs)."""


def read(obs):
    return obs.counters_start.get("serve_boot_warmup_seconds") or None
