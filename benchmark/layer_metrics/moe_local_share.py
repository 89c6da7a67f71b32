"""Model programs: of the (token, expert) pairs a model routed in the
window (a prefill's real prompt positions and a decode step's live
rows), the share routed to an expert this chip holds, %: window
differences of ``serve_moe_local_pairs_total`` /
``serve_moe_routed_pairs_total``. A chip that holds ``h`` of ``n``
experts sees ``100 h / n`` under even routing (6.25% for 16 of 256): how
much of the expert work this share of the deployment does. None on a
program without the counters (one that holds every expert it routes
over), or where nothing was routed."""


def read(obs):
    local = obs.counter_delta("serve_moe_local_pairs_total")
    routed = obs.counter_delta("serve_moe_routed_pairs_total")
    if local is None or not routed:
        return None
    return 100.0 * local / routed
