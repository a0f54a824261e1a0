"""LDA's collapsed Gibbs token loop as one Pallas TPU kernel.

The loop samples a worker's tokens in order.  A token takes its topic out
of its word's row of the word-topic table B, its document's row of the
doc-topic table D and the topic totals s̃, draws a topic from

    (γ + B[v]) / (Vγ + s̃) · (α + D[d])

and adds the new topic back.  As XLA instructions each token is about
twenty dependent operations, each waiting on the last (about 6 us a token
on a v5e).  Here the whole loop is one kernel:

* the draw is ``jax.random.categorical``'s: the argmax (lowest index on
  ties) of Gumbel noise plus the log-conditional.  The noise depends on
  nothing but the token's subkey, so XLA draws it before the kernel, a
  chunk of :data:`CHUNK` tokens at a time, with the sampler's own
  ``jax.random.gumbel``;
* the token fields sit in SMEM, a block of :data:`BLOCK` tokens a grid
  step, with the next block beside it for the look-ahead; s̃ stays in
  VMEM; B and D stay in HBM;
* each token's B and D rows are fetched by DMA :data:`AHEAD` tokens
  before it is drawn, into landing buffers, and written back by DMA from
  a ring of the last :data:`RING` tokens' rows;
* the new topic never becomes a scalar: the masks take it as a vector,
  and z is written into a vector output block.

Hazards.  A token whose row a token fewer than :data:`RING` tokens before
it left takes that row from the ring (*forwarded*), and the earlier token
does not write it back; a row read from HBM is last written at least
:data:`RING` tokens before, and every write-back is waited on
``RING - AHEAD`` tokens after it starts, before the read that could see
it.  Which tokens forward and which write back is computed from the data
before the kernel (:func:`token_table`); :func:`forwarded` counts the
rows the ring serves.  Chunks are independent kernel calls: nothing
forwards across one.

Unscheduled tokens are skipped: they read, draw and write nothing, and
their topics are the caller's to keep.

``repro.apps.lda`` runs the kernel on a TPU and its ``fori_loop`` of XLA
instructions elsewhere (``lax.platform_dependent``, as
``split_chain.subkeys``); the loop is also the kernel's oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: row groups a token may take from an earlier token without HBM (a
#: power of two)
RING = 8
#: tokens ahead whose rows are fetched by DMA (a power of two, < RING)
AHEAD = 4
#: tokens a grid step takes
BLOCK = 512
#: tokens a kernel call takes; the noise is drawn a chunk at a time
CHUNK = 8192
LANES = 128
#: rows of a tile of XLA's float32 layout on the TPU, (8, 128)
SUBLANES = 8

# the fields of a token in the SMEM table
_ROW, _DOC, _TOPIC, _FLAGS = range(4)
FIELDS = 4
# flag bits: active, then the forward distances of its B and D row
# groups (0: read from HBM), then whether it writes each group back
_ACTIVE = 1
_B_SHIFT, _D_SHIFT, _DIST_MASK = 1, 4, 7
_B_WRITE, _D_WRITE = 1 << 7, 1 << 8
_VMEM_LIMIT = 32 * 2**20


def _chunk(T: int) -> int:
    return min(CHUNK, -(-T // BLOCK) * BLOCK)


def _lanes(K: int) -> int:
    return -(-K // LANES) * LANES


def _hazards(rows, active, chunk: int):
    """(distance back to the last active token with a row in the same
    group, 0 if there is none fewer than :data:`RING` tokens back in the
    same chunk; whether such a token follows within :data:`RING`)."""
    groups = rows // SUBLANES
    pos = jnp.arange(rows.shape[0]) % chunk
    back = jnp.zeros(rows.shape, jnp.int32)
    ahead = jnp.zeros(rows.shape, jnp.bool_)
    for d in range(RING - 1, 0, -1):          # the nearest wins
        same = (active & jnp.roll(active, d)
                & (groups == jnp.roll(groups, d)) & (pos >= d))
        back = jnp.where(same, d, back)
        ahead = ahead | jnp.roll(same, -d)
    return back, ahead


def _padded(x, n: int, value=0):
    return jnp.pad(x, (0, n - x.shape[0]), constant_values=value)


def token_table(rows, docs, z, active):
    """(T', 4) int32: each token's B row, document, topic and flags, over
    tokens padded to whole chunks (the padding inactive)."""
    T = rows.shape[0]
    chunk = _chunk(T)
    n = -(-T // chunk) * chunk
    rows, docs, z = (_padded(jnp.asarray(a, jnp.int32), n)
                     for a in (rows, docs, z))
    active = _padded(active, n, False)
    b_back, b_ahead = _hazards(rows, active, chunk)
    d_back, d_ahead = _hazards(docs, active, chunk)
    flags = (active.astype(jnp.int32)
             | (b_back << _B_SHIFT) | (d_back << _D_SHIFT)
             | jnp.where(active & ~b_ahead, _B_WRITE, 0)
             | jnp.where(active & ~d_ahead, _D_WRITE, 0))
    return jnp.stack([rows, docs, z, flags], axis=1)


def forwarded(rows, docs, active):
    """The B and D row-group reads the kernel takes from its ring instead
    of HBM, over tokens ``(T,)`` (or ``(workers, T)``: summed)."""
    if rows.ndim == 2:
        return jnp.sum(jax.vmap(forwarded)(rows, docs, active))
    chunk = _chunk(rows.shape[0])
    n = -(-rows.shape[0] // chunk) * chunk
    active = _padded(active, n, False)
    return sum(jnp.sum(_hazards(_padded(a, n), active, chunk)[0] > 0,
                       dtype=jnp.int32) for a in (rows, docs))


def _kernel(cur_ref, nxt_ref, noise_ref, s_in_ref, b_in_ref, d_in_ref,
            b_ref, d_ref, s_ref, z_ref, fwd_ref,
            b_tiles, d_tiles, read_sems, write_sems, pending, *,
            K: int, alpha: float, gamma: float, vgamma: float):
    del b_in_ref, d_in_ref                   # aliased: b_ref, d_ref
    g = pl.program_id(0)
    n = pl.num_programs(0) * BLOCK
    Kp = s_ref.shape[1]
    tables = ((b_ref, b_tiles, _ROW, _B_SHIFT, _B_WRITE, 1),
              (d_ref, d_tiles, _DOC, _D_SHIFT, _D_WRITE, 2))

    def field(j, f):
        """Field ``f`` of the token ``j`` places into this block (``j`` <
        2 · BLOCK: the next block's beside this one's)."""
        here = cur_ref[FIELDS * jnp.minimum(j, BLOCK - 1) + f]
        there = nxt_ref[FIELDS * jnp.maximum(j - BLOCK, 0) + f]
        return jnp.where(j < BLOCK, here, there)

    def tile(hbm, row):
        """The HBM tile of ``row``'s group: its 8 rows and every lane up to
        the next multiple of 128 (the layout's padding; the start is
        traced, so the slice is checked against the tiled layout, not the
        logical shape)."""
        start = pl.multiple_of(row & -SUBLANES, SUBLANES)
        lane0 = pl.multiple_of(start * 0, LANES)
        return hbm.at[pl.ds(start, SUBLANES), pl.ds(lane0, Kp)]

    def read(hbm, tiles, k, row, a):
        return pltpu.make_async_copy(tile(hbm, row), tiles.at[RING + a],
                                     read_sems.at[k, a])

    def write(hbm, tiles, k, row, slot):
        return pltpu.make_async_copy(tiles.at[slot], tile(hbm, row),
                                     write_sems.at[k, slot])

    def fetch(j):
        """Start the reads of the token ``j`` places on, after the
        write-backs of its ring slot's last tenant."""
        t = g * BLOCK + j
        slot = t & (RING - 1)

        @pl.when(t < n)
        def _():
            for k, (hbm, tiles, _, _, _, bit) in enumerate(tables):
                @pl.when(pending[slot] & bit != 0)
                def _():
                    write(hbm, tiles, k, 0, slot).wait()
            pending[slot] = 0
            flags = field(j, _FLAGS)
            for k, (hbm, tiles, f, shift, _, _) in enumerate(tables):
                @pl.when((flags & _ACTIVE != 0)
                         & ((flags >> shift) & _DIST_MASK == 0))
                def _():
                    read(hbm, tiles, k, field(j, f),
                         t & (AHEAD - 1)).start()

    @pl.when(g == 0)
    def _():
        s_ref[...] = s_in_ref[...]
        fwd_ref[0] = 0
        for slot in range(RING):
            pending[slot] = 0
        for j in range(AHEAD):
            fetch(j)

    topics = lax.broadcasted_iota(jnp.int32, (1, Kp), 1)
    lanes = topics.astype(jnp.float32)
    place = (lax.broadcasted_iota(jnp.int32, z_ref.shape, 0) * LANES
             + lax.broadcasted_iota(jnp.int32, z_ref.shape, 1))

    def draw(j, s, z):
        t = g * BLOCK + j
        slot = t & (RING - 1)
        flags = cur_ref[FIELDS * j + _FLAGS]
        zi = cur_ref[FIELDS * j + _TOPIC]

        def sublane(f):
            return cur_ref[FIELDS * j + f] & (SUBLANES - 1)

        rows = []
        for k, (hbm, tiles, f, shift, _, _) in enumerate(tables):
            back = (flags >> shift) & _DIST_MASK

            @pl.when(back == 0)
            def _():
                read(hbm, tiles, k, 0, t & (AHEAD - 1)).wait()
            src = jnp.where(back == 0, RING + (t & (AHEAD - 1)),
                            (t - back) & (RING - 1))
            rows.append(tiles[src, pl.ds(sublane(f), 1), :])
            tiles[slot] = tiles[src]
            fwd_ref[0] += (back != 0).astype(jnp.int32)
        b, d = rows
        old = topics == zi
        b, d, s = (jnp.where(old, c - 1, c) for c in (b, d, s))
        logits = (jnp.log(gamma + b) - jnp.log(vgamma + s)
                  + jnp.log(alpha + d))
        noisy = jnp.where(topics < K, noise_ref[pl.ds(j, 1), :] + logits,
                          -jnp.inf)
        best = jnp.max(noisy, axis=1, keepdims=True)
        # the lowest topic that reaches it, as a float: one cross-lane
        # reduction where an int32 minimum takes two
        znew = jnp.min(jnp.where(noisy == best, lanes, float(Kp)), axis=1,
                       keepdims=True)                           # (1, 1)
        new = lanes == znew
        b, d, s = (jnp.where(new, c + 1, c) for c in (b, d, s))
        for (hbm, tiles, f, _, writes, bit), row in zip(tables, (b, d)):
            tiles[slot, pl.ds(sublane(f), 1), :] = row
        for k, (hbm, tiles, f, _, writes, bit) in enumerate(tables):
            @pl.when(flags & writes != 0)
            def _():
                write(hbm, tiles, k, cur_ref[FIELDS * j + f], slot).start()
                pending[slot] = pending[slot] | bit
        return s, jnp.where(place == j, znew.astype(jnp.int32), z)

    def step(j, carry):
        carry = lax.cond(cur_ref[FIELDS * j + _FLAGS] & _ACTIVE != 0,
                         functools.partial(draw, j), lambda s, z: (s, z),
                         *carry)
        fetch(j + AHEAD)
        return carry

    s, z = lax.fori_loop(0, BLOCK, step,
                         (s_ref[...], jnp.zeros(z_ref.shape, jnp.int32)))
    s_ref[...] = s
    z_ref[...] = z

    @pl.when(g == pl.num_programs(0) - 1)
    def _():
        for slot in range(RING):
            for k, (hbm, tiles, _, _, _, bit) in enumerate(tables):
                @pl.when(pending[slot] & bit != 0)
                def _():
                    write(hbm, tiles, k, 0, slot).wait()


def _call(table, noise, s, B, D, *, K, alpha, gamma, vgamma,
          interpret=False):
    """One chunk: ``table`` (n·4,) int32, ``noise`` (n, K') and ``s``
    (1, K') float32, K' the K topics padded to lanes; B and D are updated
    in place.  Returns (B, D, s̃ (1, K'), z (n/BLOCK, BLOCK/128, 128),
    forwarded (1,))."""
    n, Kp = noise.shape
    G = n // BLOCK
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_kernel, K=K, alpha=alpha, gamma=gamma,
                               vgamma=vgamma)
    tiles = lambda: pltpu.VMEM((RING + AHEAD, SUBLANES, Kp), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[smem((FIELDS * BLOCK,), lambda g: (g,)),
                  smem((FIELDS * BLOCK,),
                       lambda g: (jnp.minimum(g + 1, G - 1),)),
                  pl.BlockSpec((BLOCK, Kp), lambda g: (g, 0)),
                  pl.BlockSpec((1, Kp), lambda g: (0, 0)),
                  anywhere, anywhere],
        out_specs=[anywhere, anywhere,
                   pl.BlockSpec((1, Kp), lambda g: (0, 0)),
                   pl.BlockSpec((None, BLOCK // LANES, LANES),
                                lambda g: (g, 0, 0)),
                   smem((1,), lambda g: (0,))],
        out_shape=[jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(D.shape, D.dtype),
                   jax.ShapeDtypeStruct((1, Kp), jnp.float32),
                   jax.ShapeDtypeStruct((G, BLOCK // LANES, LANES),
                                        jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[tiles(), tiles(),
                        pltpu.SemaphoreType.DMA((2, AHEAD)),
                        pltpu.SemaphoreType.DMA((2, RING)),
                        pltpu.SMEM((RING,), jnp.int32)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gibbs_scan",
        interpret=interpret,
    )(table, table, noise, s, B, D)


def noise(keys, K: int):
    """(n, K) float32: the Gumbel noise ``jax.random.categorical`` adds
    to K logits, for each token's subkey (``keys``, typed keys (n,))."""
    return jax.vmap(lambda k: jax.random.gumbel(k, (K,), jnp.float32))(keys)


def _tiled(x):
    """``x`` padded to whole (8, 128) tiles: the bytes XLA's TPU layout
    holds, which the kernel's DMAs move."""
    return jnp.pad(x, ((0, -x.shape[0] % SUBLANES), (0, -x.shape[1] % LANES)))


def gibbs_scan_kernel(B, D, s, rows, docs, z, active, keys, *, alpha: float,
                      gamma: float, vgamma: float, interpret=False):
    """The Gibbs loop over tokens ``(T,)`` (their B rows, documents,
    topics, scheduled mask and typed subkeys) as Pallas kernel calls, a
    chunk at a time.  Returns (B, D, s̃, the drawn topics (T,) where a
    token is scheduled, and the forwarded count).

    ``interpret``: run the kernel in Pallas' interpreter, on B and D
    padded to whole tiles as the chip's layout holds them."""
    T, K = rows.shape[0], B.shape[1]
    Kp = _lanes(K)
    table = token_table(rows, docs, z, active)
    chunk = _chunk(T)
    chunks = table.shape[0] // chunk
    keys = jnp.concatenate(
        [keys, jnp.broadcast_to(keys[:1], (table.shape[0] - T,))])
    call = functools.partial(_call, K=K, alpha=alpha, gamma=gamma,
                             vgamma=vgamma, interpret=interpret)
    shapes = B.shape, D.shape
    if interpret:
        B, D = _tiled(B), _tiled(D)

    def one(c, carry):
        B, D, s, z, fwd = carry
        start = c * chunk
        tab = lax.dynamic_slice_in_dim(table, start, chunk)
        eps = noise(lax.dynamic_slice_in_dim(keys, start, chunk), K)
        eps = jnp.pad(eps, ((0, 0), (0, Kp - K)))
        B, D, s, zc, f = call(tab.reshape(-1), eps, s, B, D)
        z = lax.dynamic_update_slice_in_dim(z, zc.reshape(-1), start, 0)
        return B, D, s, z, fwd + f[0]

    carry = (B, D, jnp.pad(s, (0, Kp - K)).reshape(1, Kp),
             jnp.zeros((table.shape[0],), jnp.int32), jnp.int32(0))
    B, D, s, z_new, fwd = lax.fori_loop(0, chunks, one, carry)
    if interpret:
        B, D = (x[:r, :c] for x, (r, c) in zip((B, D), shapes))
    return B, D, s[0, :K], z_new[:T], fwd
