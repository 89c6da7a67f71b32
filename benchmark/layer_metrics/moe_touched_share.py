"""Model programs: of the experts a routed model's decode steps could
have streamed in the window (layers x experts, every step of every
dispatch), the share that some live row was routed to, %: window
differences of ``serve_moe_decode_experts_touched_total`` /
``serve_moe_decode_expert_slots_total``. The program reads the weights of
the touched experts only, so at a part-full batch this is the share of
the expert stream a step still pays for. None on a program without the
counters (a dense model, a commit before they existed), or where no
decode step ran."""


def read(obs):
    touched = obs.counter_delta("serve_moe_decode_experts_touched_total")
    slots = obs.counter_delta("serve_moe_decode_expert_slots_total")
    if touched is None or not slots:
        return None
    return 100.0 * touched / slots
