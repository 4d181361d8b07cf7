"""The chunked serving step unembeds only the position each slot samples
from: its row i equals the full [B, C, Vpad] logits of the same hidden
states at ``n_tok[i] - 1``, and a served workload's greedy tokens are
those of a session whose chunked step still unembeds every position."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, Request
from repro.configs import get, reduced
from repro.models import model as M
from repro.models.model import serve_logits
from repro.sched.prefill import prefill_hidden, prefill_step

SLOTS, CHUNK, PAGE, MAX_LEN = 4, 8, 8, 64
# the two programs unembed the same hidden states; only the matmul's row
# count differs, so the rows agree to f32 accumulation order
ATOL = 1e-4


def _full_logits_step(cfg, params, state, tokens, n_tok):
    """The chunked step as it was: every position unembedded."""
    state, x = prefill_hidden(cfg, params, state, tokens, n_tok)
    return state, serve_logits(cfg, params, x)            # [B, C, Vpad]


def _paged_state(cfg):
    """A decode state whose slots own disjoint pages, in order."""
    state = M.init_decode_state(cfg, SLOTS, MAX_LEN, kv_cache="paged",
                                page_size=PAGE, kv_pool_pages=SLOTS * 8 + 1,
                                kv_dtype="bf16")
    npp = state["page_table"].shape[1]
    state["page_table"] = jnp.arange(SLOTS * npp, dtype=jnp.int32
                                     ).reshape(SLOTS, npp) + 1
    return state


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "gemma2-2b", "llama3-8b"])
def test_chunked_row_is_the_full_logits_at_the_last_fed_position(arch):
    cfg = reduced(get(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    new = jax.jit(functools.partial(prefill_step, cfg))
    old = jax.jit(functools.partial(_full_logits_step, cfg))
    state = _paged_state(cfg)
    rng = np.random.default_rng(0)
    # idle, one token, a partial chunk, a full chunk; then the same slots
    # again over the history the first call wrote
    for n_tok in ([0, 1, 5, CHUNK], [CHUNK, 3, 0, 1]):
        tokens = jnp.asarray(rng.integers(1, cfg.vocab, (SLOTS, CHUNK)),
                             jnp.int32)
        n_tok = jnp.asarray(n_tok, jnp.int32)
        st_new, rows = new(params, state, tokens, n_tok)
        state, full = old(params, state, tokens, n_tok)
        rows, full = np.asarray(rows), np.asarray(full)
        assert rows.shape == (SLOTS, full.shape[-1])
        assert np.array_equal(np.asarray(st_new["pos"]),
                              np.asarray(state["pos"]))
        for i, n in enumerate(np.asarray(n_tok)):
            want = full[i, max(int(n) - 1, 0)]
            np.testing.assert_allclose(rows[i], want, rtol=0, atol=ATOL)
            assert rows[i].argmax() == want.argmax()


def _session():
    eng = Engine(reduced(get("qwen1.5-0.5b")))
    return eng.session(batch_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
                       kv_cache="paged", kv_dtype="bf16",
                       scheduler={"chunk": CHUNK})


def _serve(sess):
    # prompts shorter than, equal to and longer than a chunk, more
    # requests than slots so later prompts prefill beside decoding slots
    for r in range(7):
        sess.submit(Request(prompt=[(5 * r + j) % 97 + 1
                                    for j in range(3 + 4 * r)],
                            max_new=4 + r % 3, rid=r))
    return {res.rid: res.tokens for res in sess.run()}


def test_served_tokens_match_the_step_that_unembeds_every_position():
    sess = _session()
    got = _serve(sess)
    chunked = [r for r in sess.step_records if r["kind"] == "chunked"]
    assert chunked and all(
        r["d2h_bytes"] == SLOTS * sess.cfg.vocab * 4 for r in chunked)

    ref = _session()
    cfg = ref.cfg

    def indexed(params, state, tokens, n_tok):
        state, full = _full_logits_step(cfg, params, state, tokens, n_tok)
        last = jnp.maximum(n_tok - 1, 0)
        return state, full[jnp.arange(full.shape[0]), last]

    ref._prefill = jax.jit(indexed, donate_argnums=(1,))
    want = _serve(ref)
    assert len(want) == 7
    assert got == want
