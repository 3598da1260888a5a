"""End-to-end behaviour tests for the whole system."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import ShapeDtypeStruct as SDS

from repro.configs import get_config, reduced
from repro.core import ConProm, get_backend
from repro.containers import bloom as bl
from repro.containers import hashmap as hm
from repro.containers import hashmap_buffer as hb
from repro.containers import queue as q
from repro.data.genomics import GenomeSim, extract_kmers, pack_kmers
from repro.kernels.ops import MODE_ADD


def test_isx_bucket_sort_end_to_end(rng):
    """Paper Fig. 3: bucket sort via queue exchange, then local sort."""
    bk = get_backend(None)
    n, nbuckets = 4096, 1
    keys = rng.integers(0, 1 << 16, n).astype(np.uint32)
    spec, st = q.queue_create(bk, 8192, SDS((), jnp.uint32))
    dest = jnp.zeros(n, jnp.int32)
    st, _, dropped = q.push(bk, spec, st, jnp.asarray(keys), dest,
                            capacity=n)
    assert int(dropped) == 0
    rows, got = q.local_drain(spec, st)
    local = np.sort(np.asarray(rows)[np.asarray(got)])
    assert np.array_equal(local, np.sort(keys))


def test_kmer_counting_with_bloom(rng):
    """Paper section 9.2.2: histogram k-mers, Bloom filter pre-pass."""
    bk = get_backend(None)
    sim = GenomeSim(genome_len=1 << 10, coverage=6, error_rate=0.02, seed=1)
    kmers = pack_kmers(extract_kmers(sim.reads(), k=15))
    kspec = {"hi": SDS((), jnp.uint32), "lo": SDS((), jnp.uint32)}
    items = {"hi": jnp.asarray(kmers[:, 0]), "lo": jnp.asarray(kmers[:, 1])}
    n = kmers.shape[0]

    bspec, bst = bl.bloom_create(bk, 1 << 18, kspec, k=4)
    bst, seen_before = bl.insert(bk, bspec, bst, items, capacity=n)

    # only k-mers seen 2+ times enter the table (the paper's memory win)
    hspec, hst = hm.hashmap_create(bk, 1 << 15, kspec, SDS((), jnp.uint32),
                                   block_size=16)
    hst, ok = hm.insert(bk, hspec, hst, items,
                        jnp.ones(n, jnp.uint32), capacity=n,
                        valid=seen_before, mode=MODE_ADD, attempts=3)
    stored = int(hm.count_ready(bk, hst))
    uniq = len(np.unique(kmers, axis=0))
    assert 0 < stored < uniq          # the filter pruned singletons

    # ground-truth histogram agreement on repeated kmers
    vals, counts = np.unique(kmers, axis=0, return_counts=True)
    repeated = vals[counts >= 2]
    probe = {"hi": jnp.asarray(repeated[:, 0]),
             "lo": jnp.asarray(repeated[:, 1])}
    hst, v, found = hm.find(bk, hspec, hst, probe,
                            capacity=len(repeated) + 1,
                            promise=ConProm.HashMap.find)
    got = np.asarray(v) + 1           # first occurrence only set the bloom
    assert bool(found.all())
    assert np.array_equal(got, counts[counts >= 2])


def test_contig_generation_walk(rng):
    """Paper section 9.2.1 (Meraculous): build a de Bruijn hash table and
    walk a contig through it."""
    from repro.data.genomics import kmer_neighbors
    bk = get_backend(None)
    k = 9
    genome = rng.integers(0, 4, 64).astype(np.uint8)
    kmers = pack_kmers(extract_kmers(genome[None], k))
    n = kmers.shape[0]
    kspec = {"hi": SDS((), jnp.uint32), "lo": SDS((), jnp.uint32)}
    # value = next base after this kmer
    next_base = genome[k:].astype(np.uint32)
    hspec, hst = hm.hashmap_create(bk, 1 << 12, kspec, SDS((), jnp.uint32),
                                   block_size=16)
    hst, ok = hm.insert(bk, hspec, hst,
                        {"hi": jnp.asarray(kmers[:-1, 0]),
                         "lo": jnp.asarray(kmers[:-1, 1])},
                        jnp.asarray(next_base), capacity=n, attempts=3)
    assert bool(ok.all())

    # walk from the first kmer, reconstruct the genome
    cur = kmers[0]
    out = list(genome[:k])
    for _ in range(len(genome) - k):
        probe = {"hi": jnp.asarray([cur[0]]), "lo": jnp.asarray([cur[1]])}
        hst, v, found = hm.find(bk, hspec, hst, probe, capacity=4,
                                promise=ConProm.HashMap.find)
        if not bool(found[0]):
            break
        b = int(v[0])
        out.append(b)
        nbrs = kmer_neighbors(cur[None], k)
        cur = np.asarray(nbrs[b][0])
    assert np.array_equal(np.asarray(out), genome)


def test_hashmap_buffer_speedup_structure():
    """Buffered insertion does one exchange for the whole phase; direct
    insertion does one per call (the paper's 10x mechanism)."""
    from repro.core import costs
    bk = get_backend(None)
    kspec = SDS((), jnp.uint32)
    mspec, mstate = hm.hashmap_create(bk, 4096, kspec, kspec, block_size=16)
    keys = jnp.arange(256, dtype=jnp.uint32)

    with costs.recording() as direct:
        st = mstate
        for i in range(8):
            st, _ = hm.insert(bk, mspec, st, keys[i * 32:(i + 1) * 32],
                              keys[i * 32:(i + 1) * 32], capacity=64,
                              return_success=False, attempts=1)
    with costs.recording() as buffered:
        bspec, bstate = hb.create(bk, mspec, mstate, queue_capacity=512,
                                  buffer_cap=512)
        for i in range(8):
            bstate, _ = hb.insert(bspec, bstate, keys[i * 32:(i + 1) * 32],
                                  keys[i * 32:(i + 1) * 32])
        bstate, _ = hb.flush(bk, bspec, bstate, capacity=512)
    n_coll_direct = direct.total().collectives
    n_coll_buffered = buffered.total().collectives
    assert n_coll_buffered < n_coll_direct


def test_tiny_training_learns(mesh11, tmp_path):
    """~100k-param model on structured synthetic data: loss must drop."""
    from repro.data.tokens import TokenStream
    from repro.launch.steps import init_state, make_train_step
    cfg = reduced(get_config("stablelm-1.6b"))
    rng = jax.random.PRNGKey(0)
    params, opt, _, _ = init_state(cfg, mesh11, rng)
    step_fn = jax.jit(make_train_step(cfg, mesh11), donate_argnums=(0, 1))
    stream = TokenStream(vocab=cfg.vocab, seq_len=64, global_batch=4)
    losses = []
    for _ in range(30):
        batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
        params, opt, m = step_fn(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; unset, the
    cache is the checkout's fixed .jax_cache, whatever the cwd."""
    from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.chdir(tmp_path)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(CHECKOUT_CACHE)
            assert CHECKOUT_CACHE.parent == pathlib.Path(__file__).resolve().parents[1]
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
