"""tiny-olmoe (MHA, whole-projection QK-norm, 8 experts top-4 without
renormalisation) through the scheduler, end to end on the CPU: every
prefill program it has, the stack the benchmark serves with, the two
counters of what the capacity buckets drop and the one of the tile rows
its dropless prefills multiply. A module of its own, so that
its programs are freed before the next module's (tests/conftest.py)."""

import threading

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo, generate as run

TOK = ByteTokenizer(vocab_size=get_config("tiny-olmoe").vocab_size)


def test_olmoe_admission_widths_chunks_prefix_and_drop_counters():
    """tiny-olmoe through every prefill program the scheduler has, on
    the benchmark's stack (int8 weights, paged int8 cache, prefix store,
    fused decode, a chunk ladder): a lone request (the 1-row programs),
    eight at once (the wide ones), a prompt longer than a chunk (first /
    mid / final), prompts that share the registered head (prefix admit).
    Greedy output equals a solo dense-cache loop on the same tree, and
    the two drop counters count exactly the real prompt positions the
    prefill programs computed: ``serve_prefill_tokens_total`` (suffixes
    of admitted prompts) plus the prefix build's tokens, times top-k,
    times layers. No bucket is bounded here (no capacity factor), so
    nothing is dropped."""
    from p2p_llm_chat_tpu.models import mixtral

    mcfg = get_config("tiny-olmoe")
    assert mcfg.moe_capacity_factor is None
    qparams = mixtral.init_params_quantized(mcfg, jax.random.PRNGKey(4))
    # The solo loop on the model layer's dense cache (tests/solo.py).
    solo = Solo(mixtral, mcfg, TOK, max_seq=256, dtype=jnp.bfloat16)
    head = "olmoe shared head, "
    eng = TPUEngine(qparams, mcfg, TOK, num_slots=8, max_seq=256,
                    page_size=16, kv_quant=True,
                    prefix_cache=True, prefix_texts=(head,),
                    decode_fuse_max=4, prefill_chunk=32)
    try:
        # Before traffic, as warm-up does for a deployment's templates.
        built = eng.scheduler.register_prefix(head)
        assert built == len(TOK.encode(head, add_bos=True)) - 1
        lone = "a request that arrives alone"
        long = head + "x" * 90          # suffix bucket 128: four chunks
        burst = [head + f"burst {i}" for i in range(6)] + [
            f"no head {i}" for i in range(2)]
        assert run(eng, lone, max_tokens=6)[0] == solo(qparams, lone, 6)
        assert run(eng, long, max_tokens=6)[0] == solo(qparams, long, 6)
        got, errs = {}, []

        def worker(p):
            try:
                got[p] = run(eng, p, max_tokens=9)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in burst]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs, errs
        assert got == {p: solo(qparams, p, 9) for p in burst}
        m = eng.metrics_snapshot()
        assert m["serve_admitted_total"] == 10
        # Pair by pair: a dummy entry only where a group was odd.
        assert (m["serve_admitted_total"]
                <= m["serve_admit_rows_padded_total"]
                <= m["serve_admitted_total"] + m["serve_admit_batches_total"])
        assert m["prefill_chunks_total"] >= 3
        assert m["serve_prefix_admits_total"] >= 7
        assert m["decode_fused_ticks_total"] > 0
        per_token = mcfg.num_experts_per_tok * mcfg.num_layers
        assert m["serve_moe_assignments_total"] == per_token * (
            m["serve_prefill_tokens_total"] + built)
        assert m["serve_moe_dropped_total"] == 0
        # The admissions ran tiles (a dropless prefill on one device):
        # the rows they multiplied are the pairs and each run's last
        # tile's padding, under an expert's worth of tiles a layer a
        # dispatch, far from the buckets' num_experts / top-k = 2 rows a
        # pair over the real share of the positions.
        rows = m["serve_moe_prefill_rows_total"]
        assert m["serve_moe_assignments_total"] <= rows
        dispatches = (m["serve_admit_batches_total"]
                      + m["prefill_chunks_total"] + 1)      # + the build
        assert rows < m["serve_moe_assignments_total"] + (
            dispatches * mcfg.num_layers * mcfg.num_experts * 128)
    finally:
        eng.stop()
