"""Scheduler, seen by the clients: output tokens read inside the window
over the window's seconds. What an operator's chip delivers above
capacity. Recorded, not judged: which of a window's few heaviest
prompts fall inside it moves it by 3-5% between seeds, and at a full
batch ``tpot_p50_ms`` carries the same fact (tokens/s is about live
rows / time per token) and repeats within 2% (PERF.md, PR 22)."""
from benchmark.metrics import end_to_end


def read(obs):
    return end_to_end(obs)["out_tok_s"]
