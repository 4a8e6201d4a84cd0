"""The paged-attention kernel (interpreted here) against the gather
formulation it replaces in the decode program, on the same pool.

The reference below is ``serving/model.py::_build_decode``'s
``attend_gathered`` with float32 arithmetic: gather every slot's whole
window through its table, mask by position, softmax, weigh. The kernel
reads only the pages a context occupies, so what lies anywhere else may be
anything: the pools here hold NaN in every page no table names.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import paged_attention

BS, MAXB, NB = 16, 12, 48  # tokens a page, pages a slot's window, pages a layer
FULL = MAXB * BS - 1  # the last position of the window


def gathered(q, pool, tables, lens, scale):
    B, H, hd = q.shape
    S = tables.shape[1] * pool.shape[1]
    kv = pool.shape[2] // (2 * hd)  # K|V heads a row; query head i reads head i // (H / kv)
    ctx = jnp.repeat(pool[tables].reshape(B, S, kv, 2 * hd).astype(jnp.float32), H // kv, axis=2)
    kk, vv = ctx[..., :hd], ctx[..., hd:]
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kk) * scale
    valid = jnp.arange(S)[None, :] <= lens[:, None]
    a = jax.nn.softmax(jnp.where(valid[:, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bhs,bshd->bhd", a, vv).reshape(B, -1)


def make_case(lens, H, hd, dtype, layer=0, seed=0, kv=None, maxb=MAXB, nb=NB):
    """q, a pool of ``layer + 1`` layers, layer-folded tables and lens:
    each slot owns scattered pages of layer ``layer`` for its context
    (an empty slot none); table entries behind them name scratch block 0;
    every page that no table names holds NaN, those of other layers too.
    ``kv``: K|V heads a row, fewer than the ``H`` query heads (grouped
    queries); None: one a query head. ``maxb``, ``nb``: pages a slot's
    window and a layer hold."""
    r = np.random.RandomState(seed)
    B, row = len(lens), (kv or H) * 2 * hd
    pool = np.full(((layer + 1) * nb, BS, row), np.nan, np.float32)
    base = layer * nb
    tables = np.zeros((B, maxb), np.int32)
    free = list(r.permutation(np.arange(1, nb)))
    pool[base] = r.randn(BS, row)  # whatever idle slots last wrote there
    for b, n in enumerate(lens):
        for j in range(0 if n == 0 else n // BS + 1):
            tables[b, j] = free.pop()
            pool[base + tables[b, j]] = r.randn(BS, row)
    q = jnp.asarray(r.randn(B, H, hd), dtype)
    return (q, jnp.asarray(pool, dtype), jnp.asarray(tables + base),
            jnp.asarray(np.asarray(lens, np.int32)))


def check(q, pool, tables, lens):
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = np.asarray(paged_attention(q, pool, tables, lens, scale), np.float32)
    assert out.shape == (q.shape[0], q.shape[1] * q.shape[2]) and not np.isnan(out).any()
    want = np.asarray(gathered(q, pool, tables, lens, scale))
    tol = 2e-5 if pool.dtype == jnp.float32 else 2e-2  # as tests/test_flash_attention.py
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,hd", [(5, 64), (3, 128)])
@pytest.mark.parametrize("n", [1, BS - 1, BS, BS + 1, 8 * BS - 1, 8 * BS, FULL])
def test_one_length_against_the_gathered_window(n, H, hd, dtype):
    """A context of n + 1 tokens (the new one at position n): around a
    page's edge, around a step's edge (8 pages), and the whole window."""
    check(*make_case([n], H, hd, dtype))


@pytest.mark.parametrize("H,hd", [(5, 64), (25, 64), (16, 128)])
def test_ragged_batch_with_an_empty_slot_beside_full_ones(H, hd):
    """Every slot by its own length; slot 0 is empty (position 0, a table
    of scratch blocks): it costs one page and disturbs no neighbour."""
    lens = [0, FULL, 1, BS, 0, 3 * BS + 5, FULL, BS - 1]
    q, pool, tables, ln = make_case(lens, H, hd, "bfloat16")
    out = check(q, pool, tables, ln)
    # an empty slot attends to position 0 of the scratch block alone: its V
    v0 = np.asarray(pool[tables[0, 0], 0].astype(jnp.float32)).reshape(H, 2, hd)[:, 1]
    np.testing.assert_allclose(out[0], v0.reshape(-1), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,kv,hd", [(32, 8, 64), (16, 8, 128)])
def test_grouped_queries_read_their_groups_kv_head(H, kv, hd, dtype):
    """32 query heads over 8 K|V heads: head ``i`` attends K|V head ``i //
    4`` and no other (the gathered formulation repeats K and V four
    times), ragged, an empty slot among full ones, on a later layer."""
    lens = [0, FULL, 1, BS, 3 * BS + 5, 8 * BS - 1]
    q, pool, tables, ln = make_case(lens, H, hd, dtype, layer=1, kv=kv)
    out = check(q, pool, tables, ln)
    # a K|V head's four query heads differ (their q does), and moving query
    # head 5's q moves its own output alone
    q2 = q.at[:, 5].add(1.0)
    again = np.asarray(paged_attention(q2, pool, tables, ln, 1.0 / math.sqrt(hd)), np.float32)
    same = np.isclose(again.reshape(len(lens), H, hd), out.reshape(len(lens), H, hd)).all(-1)
    assert same[1:, [h for h in range(H) if h != 5]].all() and not same[1:, 5].any()


def test_a_group_that_does_not_fill_the_accumulators_tiles_is_refused():
    assert "4 query heads over 2 K|V heads" in pa.unsupported(64, 16, jnp.bfloat16, 4, 2)
    assert "5 query heads over 2" in pa.unsupported(64, 16, jnp.bfloat16, 5, 2)
    assert pa.unsupported(64, 16, jnp.bfloat16, 32, 8) == pa.unsupported(64, 16, jnp.bfloat16, 25, 25) == ""
    q, pool, tables, ln = make_case([5], 4, 64, "float32", kv=2)
    with pytest.raises(ValueError, match="not a whole group"):
        paged_attention(q, pool, tables, ln, 0.125)
    assert pa.vmem_scratch_bytes(32, 64, 16, jnp.bfloat16, 8) < pa.vmem_scratch_bytes(32, 64, 16, jnp.bfloat16)


@pytest.mark.parametrize("layer", [0, 3])
def test_layer_base_is_part_of_the_table(layer):
    """The layer is folded into the page index (``i * NB + block``): the
    kernel reads layer ``layer``'s pages and no other layer's (NaN)."""
    check(*make_case([2 * BS + 3, 0, FULL], 5, 64, "bfloat16", layer=layer))


def test_pages_a_slot_does_not_own_never_reach_its_output():
    """NaN everywhere but in the pages the tables name; and a slot's
    result is the same whatever lies in its neighbours' pages."""
    lens = [BS + 2, 5 * BS, 9]
    q, pool, tables, ln = make_case(lens, 5, 64, "float32")
    out = check(q, pool, tables, ln)
    other = np.array(pool)
    for j in range(lens[1] // BS + 1):  # slot 1's pages, rewritten
        other[int(tables[1, j])] = 7.0
    again = np.asarray(paged_attention(q, jnp.asarray(other), tables, ln, 1.0 / 8.0))
    np.testing.assert_array_equal(again[[0, 2]], out[[0, 2]])
    assert not np.allclose(again[1], out[1])


def test_positions_behind_the_new_token_are_masked_in_its_page():
    """What the last page holds past position n (an earlier owner's rows)
    does not count: the result is that of a pool holding zeros there."""
    n = 2 * BS + 4
    q, pool, tables, ln = make_case([n], 5, 64, "float32")
    last = int(tables[0, n // BS])
    loud = np.array(pool)
    loud[last, n % BS + 1:] = 1e4
    a = paged_attention(q, pool, tables, ln, 0.125)
    b = paged_attention(q, jnp.asarray(loud), tables, ln, 0.125)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tables_may_share_pages():
    """Two slots whose tables name the SAME pages (a shared prefix, or
    idle slots on scratch block 0) read them independently."""
    q, pool, tables, ln = make_case([3 * BS + 1, 3 * BS + 1, 0, 0], 5, 64, "bfloat16")
    tables = tables.at[1].set(tables[0])
    out = check(q, pool, tables, ln)
    assert not np.allclose(out[0], out[1])  # same pages, another q


@pytest.mark.parametrize("hd,bs,dtype,ok", [
    (64, 16, "bfloat16", True), (128, 16, "bfloat16", True), (64, 8, "float32", True),
    (32, 16, "bfloat16", False),  # 64 lanes a head: the runtime pads the row
    (96, 16, "bfloat16", False),  # 192 lanes
    (64, 8, "bfloat16", False),  # half a bfloat16 tile a page
    (64, 48, "float32", False),  # a page that does not divide a step
])
def test_geometries_the_kernel_takes_and_refuses(hd, bs, dtype, ok):
    assert (pa.unsupported(hd, bs, dtype) == "") == ok
    if not ok:
        with pytest.raises(ValueError, match="paged_attention"):
            paged_attention(jnp.zeros((1, 2, hd), dtype), jnp.zeros((4, bs, 4 * hd), dtype),
                            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 1.0)


def test_decode_model_picks_the_path_from_what_it_is():
    """One device and a head of whole 128-lane tiles take the kernel; a
    narrower head, or a mesh, keeps the gathered window. No knob."""
    from paddle_tpu import serving

    def model(n_head, d_model, **kw):
        cfg = serving.GPTConfig(vocab_size=64, n_layer=1, n_head=n_head, d_model=d_model,
                                max_seq_len=64)
        return serving.DecodeModel(cfg, max_batch=2, n_blocks=8, block_size=16,
                                   prefill_buckets=[16], **kw)

    assert model(2, 128).attention_path() == ("kernel", "")
    path, why = model(4, 128).attention_path()
    assert path == "gather" and "64 lanes" in why
    path, why = model(2, 128, recipe="tp").attention_path()
    assert path == "gather" and "mesh" in why


# -- the engine through the kernel (a head of 64: one 128-lane tile) -----


@pytest.fixture(scope="module")
def kernel_model():
    from paddle_tpu import serving

    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=2, d_model=128, max_seq_len=64)
    dm = serving.DecodeModel(cfg, max_batch=4, n_blocks=16, block_size=16, prefill_buckets=[16, 32],
                             seed=3)
    assert dm.attention_path() == ("kernel", "")
    return dm


def test_engine_tokens_through_the_kernel_are_the_full_forwards_argmax(kernel_model):
    """Continuous batching over the kernel: every served token is the
    greedy token of the non-paged forward, and a request decodes to the
    same tokens alone as beside others (contexts cross a page's edge)."""
    from paddle_tpu import serving
    from paddle_tpu.serving import ledger

    ledger.reset()
    r = np.random.RandomState(0)
    prompts = [list(r.randint(1, 128, size=n)) for n in (5, 14, 9, 16)]
    eng = serving.ServingEngine(kernel_model)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    batched = [h.result(timeout=5) for h in handles]
    for p, got in zip(prompts, batched):
        toks = list(p)
        for _ in range(6):
            toks.append(int(kernel_model.full_logits(np.asarray(toks))[0, -1].argmax()))
        assert toks[len(p):] == got
    alone = serving.ServingEngine(kernel_model)
    for p, got in zip(prompts, batched):
        h = alone.submit(p, max_new_tokens=6)
        alone.run_until_idle()
        assert h.result(timeout=5) == got
    ledger.reset()


def test_ledger_counts_the_pages_a_tick_reads_and_the_window(kernel_model, tmp_path):
    """``attn_pages_read``: per decode tick, the pages its live slots'
    contexts occupy with the new token; ``attn_pages_window``: every
    slot's whole window. In totals(), on /status, in the journal, merged
    as sums, cleared by reset()."""
    import json

    from paddle_tpu import serving
    from paddle_tpu.serving import ledger

    ledger.reset()
    eng = serving.ServingEngine(kernel_model)
    handles = [eng.submit(list(range(1, n + 1)), max_new_tokens=4) for n in (14, 3)]
    eng.run_until_idle()
    assert all(len(h.result(timeout=5)) == 4 for h in handles)
    t = ledger.totals()
    # 3 decode ticks each: contexts 14, 15, 16 read 1, 1, 2 pages (the new
    # token opens the second page at position 16); 3, 4, 5 read 1 each
    assert t["decode_ticks"] == 3 and t["attn_pages_read"] == (1 + 1 + 2) + 3
    assert t["attn_pages_window"] == 3 * kernel_model.max_batch * kernel_model.max_blocks_per_req == 48
    att = ledger.status()["attention"]
    assert att == {"pages_read": 7, "pages_window": 48, "window_share": 7 / 48,
                   "layers": kernel_model.cfg.n_layer,  # every layer of this model attends
                   "steps": 6, "pages_a_step": 7 / 6}  # a step a slot and tick: no context passes a step's pages
    with open(ledger.flush(str(tmp_path / "serving.rank0.json"))) as f:
        journal = json.load(f)
    assert (journal["attn_pages_read"], journal["attn_pages_window"]) == (7, 48)
    merged = ledger.merge_ledgers([journal, journal])
    assert (merged["attn_pages_read"], merged["attn_pages_window"]) == (14, 96)
    assert journal["attn_layers"] == merged["attn_layers"] == kernel_model.cfg.n_layer  # a gauge: not summed
    ledger.reset()
    assert ledger.totals()["attn_pages_read"] == ledger.totals()["attn_pages_window"] == 0
    assert "attention" not in ledger.status()


# -- the kernel's step: a rule over what the kernel sees, and its counter --


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("edge", ["a_step_less_one", "a_step", "a_step_and_one", "two_steps_and_a_page",
                                  "the_windows_last"])
def test_grouped_queries_around_the_edges_of_the_kernels_step(edge, dtype):
    """32 query heads over 8 K|V heads (LFM2's row), a window of two of
    the kernel's steps at this row and two pages more: the edge's slot
    between an empty neighbour and ragged ones, behind a longer slot whose
    rows its buffers still hold."""
    H, kv, hd, bs = 32, 8, 64, BS
    step = pa.step_schedule(kv * 2 * hd * jnp.dtype(dtype).itemsize).positions
    maxb = 2 * step // bs + 2
    n = {"a_step_less_one": step - 1, "a_step": step, "a_step_and_one": step + 1,
         "two_steps_and_a_page": 2 * step + bs - 1, "the_windows_last": maxb * bs - 1}[edge]
    lens = [2 * step + 3, n, 0, 37, 95]
    check(*make_case(lens, H, hd, dtype, layer=1, kv=kv, maxb=maxb, nb=len(lens) * maxb + 1))



@pytest.mark.parametrize("heads,kv,hd,latent,want", [
    (25, 25, 64, (), (128, 2, False)),  # gpt2-xl: 6,400 B a position in bfloat16
    (16, 16, 128, (), (128, 2, False)),  # olmoe: 8,192 B
    (12, 12, 64, (), (256, 2, True)),  # gpt2-small: 3,072 B, between what the sweep measured
    (32, 8, 64, (), (512, 2, True)),  # lfm2: 2,048 B, four query heads a K|V head: a buffer of 1 MiB
    (64, 0, 0, (640, 512), (512, 2, True)),  # ax-k1: one latent row of 1,280 B for 64 heads
    (64, 0, 0, (1280, 1024), (256, 2, True)),  # twice the row: half the positions fill a buffer
    (64, 0, 0, (3200, 2048), (128, 2, False)),  # a latent row as wide as gpt2-xl's walks as it does
], ids=["gpt2xl_6400", "olmoe_8192", "gpt2s_3072", "lfm2_2048", "axk1_latent_1280", "latent_2560", "latent_6400"])
def test_the_step_follows_from_the_rows_bytes_in_both_modes(heads, kv, hd, latent, want):
    """The rows that sit on their bytes keep 128 positions a step, two
    buffers and a wait a page (their programs are the parent's); a narrower
    row, latent or per head, takes the positions that fill a buffer of
    1 MiB, 512 at most, and batches its copies: the schedule the sweep
    kept at both rows it measured. ``unsupported`` takes each, and
    ``vmem_scratch_bytes`` is what ``_scratch`` allocates under the same
    rule."""
    from jax.experimental.pallas import tpu as pltpu

    lanes, v_lanes = latent or (kv * 2 * hd, 0)
    step = pa.step_schedule(lanes * 2)
    assert tuple(step) == want and step.positions % 128 == 0
    assert pa.unsupported(hd, 16, jnp.bfloat16, heads, kv, latent=latent or None) == ""
    hp = -(-heads // 16) * 16  # heads in whole bfloat16 tiles
    scratch = pa._scratch(step, lanes, hp, jnp.bfloat16, v_lanes)
    vmem = sum(math.prod(ref.shape) * jnp.dtype(ref.dtype).itemsize for ref in scratch
               if getattr(ref, "memory_space", None) == pltpu.VMEM)
    assert vmem == pa.vmem_scratch_bytes(heads, hd, 16, jnp.bfloat16, kv, latent=latent)
    assert vmem == (step.buffers * step.positions * lanes * 2 + hp * (v_lanes or lanes) * 4
                    + 2 * hp * 128 * 4)


def test_ledger_counts_the_steps_the_kernel_takes(tmp_path):
    """``attn_steps``: per decode tick, the steps its live slots' pages
    take at the pages a step of THIS model's kernel holds (8: ten heads of
    64 in float32 are a row of 5,120 B, which keeps 128 positions a step),
    by hand on three ticks of a context that crosses a step's edge beside
    a short one; in totals(), on /status, in the journal, merged as sums,
    cleared by reset()."""
    import json

    from paddle_tpu import serving
    from paddle_tpu.serving import ledger

    def eng_pages(model):  # what an engine over the model counts its steps by
        return serving.ServingEngine(model)._step_pages

    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=10, d_model=640, max_seq_len=192)
    dm = serving.DecodeModel(cfg, max_batch=2, n_blocks=32, block_size=16, prefill_buckets=[16, 128], seed=3)
    assert dm.attention_path() == ("kernel", "") and eng_pages(dm) == 8
    ledger.reset()
    eng = serving.ServingEngine(dm)
    handles = [eng.submit(list(range(1, n + 1)), max_new_tokens=4) for n in (126, 3)]
    eng.run_until_idle()
    assert all(len(h.result(timeout=5)) == 4 for h in handles)
    t = ledger.totals()
    # contexts 126, 127, 128 read 8, 8, 9 pages with the new token: 1, 1, 2
    # steps of 8 pages; contexts 3, 4, 5 one page and one step each
    assert t["decode_ticks"] == 3 and t["attn_pages_read"] == (8 + 8 + 9) + 3
    assert t["attn_steps"] == (1 + 1 + 2) + 3
    att = ledger.status()["attention"]
    assert (att["steps"], att["pages_a_step"]) == (7, 28 / 7)
    with open(ledger.flush(str(tmp_path / "serving.rank0.json"))) as f:
        journal = json.load(f)
    assert journal["attn_steps"] == 7
    assert ledger.merge_ledgers([journal, journal])["attn_steps"] == 14
    ledger.reset()
    assert ledger.totals()["attn_steps"] == 0
    # a row of 1,024 B walks 32 pages a step; a model whose heads the kernel
    # cannot take gathers its window: no step
    assert eng_pages(serving.DecodeModel(
        serving.GPTConfig(vocab_size=64, n_layer=1, n_head=2, d_model=128, max_seq_len=64),
        max_batch=2, n_blocks=8, block_size=16, prefill_buckets=[16])) == 32
    narrow = serving.DecodeModel(serving.GPTConfig(vocab_size=64, n_layer=1, n_head=4, d_model=128, max_seq_len=64),
                                 max_batch=2, n_blocks=8, block_size=16, prefill_buckets=[16])
    assert narrow.attention_path()[0] == "gather" and narrow.attention_step() is None and eng_pages(narrow) == 0
