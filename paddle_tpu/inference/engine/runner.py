"""Device half of the engine core: weights, paged-KV buffers, and the jitted
prefill / decode / verify programs, all pinned to ONE mesh slice.

:class:`ModelRunner` owns everything that lives on (or dispatches to) the
accelerator: the stacked ``[L, ...]`` weight arrays with their pp×mp
NamedShardings, the paged KV cache arrays, the per-shape jitted program
caches, the copy-on-write device page copy, and the page gather/scatter
primitives the disaggregated engine's KV handoff is built from.  It holds NO
scheduling state — no queues, no refcounts, no request objects — so two
runners over disjoint mesh slices (prefill vs decode) can serve one logical
engine.

TPU-native design (carried over from the monolithic serving engine):
- TWO jitted programs serve a colocated engine: a PREFILL step consuming a
  CHUNK of prompt tokens for one slot per dispatch (chunk rows ride the
  paged-attention kernel's batch dim with per-row context lengths, so causal
  masking falls out of ctx=pos+1), and a DECODE step feeding every in-flight
  slot its last token — token-level continuous batching (Orca-style).  A
  third VERIFY program scores K+1 consecutive positions per request for
  speculative decoding.
- A decode dispatch is LAUNCHED, not awaited: ``run_decode`` returns at
  once with a handle that waits when the host asks for the tokens, and the
  program keeps each row's last token on the device for the dispatch after
  it, which takes it from there for the rows the engine names. So the
  engine launches step N+1 before it reads step N (``LLMEngine.step``).
- A model that GENERATES BY BLOCKS (``plan.block`` = Q > 0) gets another
  decode program under the same name and the same call (``_build_block``):
  a dispatch is ONE BLOCK of Q positions a live sequence - up to
  ``plan.denoising_steps`` forwards of B x Q rows that each unmask some of
  the block's positions, and one that commits the finished block's keys
  and values - and its prefill chunk sees whole blocks and samples nothing.
- Sampling happens IN-GRAPH with per-slot parameters (greedy / temperature /
  top-k / top-p / seed), replicating models.llama._sample token-for-token.
  What a dispatch's rows ask for decides the work (``_sample_rows``): when
  every row is greedy (idle slots are sent greedy) the program takes the
  arg-max and nothing else; one sampled row sends all rows through the
  filter. The branch is ONE ``lax.cond`` on the whole batch, outside the
  ``vmap`` over rows: on a per-row predicate under ``vmap`` it would lower
  to a select that runs both branches.
- KV lives in PAGES [L, n_pages, page, KVH, D]; page tables arrive from the
  scheduler per dispatch.  Pages are just indices here — allocation policy
  (refcounts, prefix cache, preemption) is the PagePool's business.
- The model says what it is (``models/serving_plan.py``): ``model.config``
  and ``model.serving_plan(kernels)``, which gives its KINDS of layer on raw
  arrays, each in two halves around what the runner does for it, the PERIOD
  they run in, its weights leaf by leaf (stacked by kind) and how they
  split (periods or layers over the pp axis, head/ffn dims over the mp
  axis; GSPMD inserts the collectives). The dense Llama block is one kind
  (``block_qkv`` / ``block_out`` around the attention) and one period; a
  model whose layers differ runs a ``lax.scan`` for each run of like layers
  and one around the period. What is here is the engine's: the pools BY
  KIND OF CACHE, all carried through every loop and donated -
  pages ``[L_pages, n_pages, page, KVH, D]`` for the layers that attend (a
  layer's own index among them shifts the tables), and for the layers
  with a recurrence a state pool ``[L_state, max_batch + 1, heads, dk,
  dv]`` float32 and the tails of their convolutions ``[L_state, max_batch
  + 1, taps - 1, channels]``, a row a slot and one more that idle rows
  write to - the write of a step's rows into them, which attention or
  recurrence reads them, and the sums of the layers' routing counts.
"""
from __future__ import annotations

import types

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import observability as _obs
from ...core.device import Place
from ...models.llama import rms_norm

__all__ = ["ModelRunner"]

_MAXK = 64        # static cap for per-slot dynamic top-k filtering
# the rows of the routing-count sums: which program a layer's call was in
_COUNT_ROWS = ("decode", "prefill")
# what a block program counts on the device beside them: its forwards by
# kind, and the live sequences summed over all of them
BLOCK_COUNTS = ("denoise", "commit", "sequence_forwards")


def _kernel_applies(device, mesh):
    """Whether the Pallas paged-attention kernels serve this placement: on a
    TPU, when the attention operands are whole on one device — no mesh, or a
    one-device mesh (how a replica is pinned to its own chip).  A mesh of
    several devices splits heads (mp) or layers (pp) across them, which the
    kernels do not take; it runs the ``*_ref`` path (ROADMAP M8)."""
    return Place(device).is_tpu_place() and (mesh is None or mesh.size == 1)


def _sort_desc(probs):
    """``probs`` [V] in descending order, and the index each came from:
    ``probs[argsort(-probs)]`` and ``argsort(-probs)``, bit for bit, ties in
    index order. One stable key-value sort: ``argsort`` sorts the same (key,
    index) pairs and drops the keys, and fetching them again is a gather of
    V single elements a row — on the TPU that gather was the sampler's cost
    (PERF.md section 6, PR 31)."""
    neg, idx = jax.lax.sort_key_val(
        -probs, jnp.arange(probs.shape[-1], dtype=jnp.int32), is_stable=True)
    return -neg, idx


def _sample_row(logits, temp, topp, topk, seed):
    """One SAMPLED row of in-graph sampling, replicating
    models.llama._sample + ops.top_p_sampling (same filter order, same sort,
    same categorical key/shape) so a SEEDED top_p<1 engine decode ==
    model.generate. (At top_p>=1.0, generate falls through to
    ops.multinomial on the global RNG stream, which ignores the seed — no
    parity is possible there by construction.) logits [V] f32; scalars
    traced. Greedy rows never need this: ``_sample_rows`` decides."""
    maxk = min(_MAXK, logits.shape[-1])
    l = logits / jnp.where(temp > 0, temp, 1.0)
    probs = jax.nn.softmax(l)
    # top-k (0 = off): zero everything below the k-th largest prob
    kvals, _ = jax.lax.top_k(probs, maxk)
    thresh = kvals[jnp.clip(topk - 1, 0, maxk - 1)]
    probs = jnp.where((topk > 0) & (probs < thresh), 0.0, probs)
    probs = probs / jnp.sum(probs)
    # top-p over the full sorted vocab (ops.top_p_sampling's formulation)
    sorted_p, sort_idx = _sort_desc(probs)
    cum = jnp.cumsum(sorted_p)
    keep = jnp.where(topp < 1.0, (cum - sorted_p) < topp, sorted_p >= 0)
    filtered = jnp.where(keep, sorted_p, 0.0)
    filtered = filtered / jnp.sum(filtered)
    key = jax.random.PRNGKey(seed)
    # [1, V] shape matches the b=1 categorical in ops.top_p_sampling, so the
    # gumbel draw is bit-identical at equal keys
    choice = jax.random.categorical(
        key, jnp.log(jnp.maximum(filtered, 1e-30))[None, :], axis=-1)[0]
    return sort_idx[choice]


def _sample_rows(logits, greedy, temp, topp, topk, seeds):
    """Next token of every row of one dispatch: logits [N, V] f32, the
    per-row parameters [N]. All rows greedy (idle slots and the rows of an
    idle verify slot arrive greedy): the arg-max alone. Any sampled row:
    every row through ``_sample_row``, greedy rows keeping their arg-max.

    The predicate is a scalar over the batch and the ``cond`` sits outside
    the ``vmap``, so the compiled program holds a real conditional and a
    greedy batch never sorts the vocabulary."""
    amax = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled():
        tok = jax.vmap(_sample_row)(logits, temp, topp, topk, seeds)
        return jnp.where(greedy > 0, amax, tok)

    return jax.lax.cond(jnp.all(greedy > 0), lambda: amax, sampled)


def _sample_rows_conf(logits, greedy, temp, topp, topk, seeds):
    """``_sample_rows`` and, beside each row's token, its probability under
    the row's own distribution (softmax of the logits over the row's
    temperature; a greedy row's over 1): the confidence that orders the
    unmasking of a block."""
    tok = _sample_rows(logits, greedy, temp, topp, topk, seeds)
    scaled = logits / jnp.where((greedy > 0) | (temp <= 0), 1.0,
                                temp)[:, None]
    at = jnp.take_along_axis(scaled, tok[:, None], axis=1)[:, 0]
    return tok, jnp.exp(at - jax.nn.logsumexp(scaled, axis=-1))


def _argmax_only(greedy):
    """The host's reading of ``_sample_rows``' predicate, for the
    ``runner.dispatch`` span."""
    return int(np.all(np.asarray(greedy) > 0))


class _DecodeTokens:
    """One decode dispatch's tokens, on the device until somebody asks:
    ``np.asarray`` of it is host tokens [k, B], the routing counts a model
    sends home behind them taken off. The first asking waits for the
    program (``runner.wait``); dispatches are read in the order they were
    launched. A block dispatch's tokens are the block's positions ``[Q,
    B]``, and ``steps`` (after the first asking) says at which denoising
    step each was unmasked (-1: it came known)."""

    __slots__ = ("_runner", "_dev", "_host", "_rows", "steps")

    def __init__(self, runner, dev, rows):
        self._runner, self._dev, self._host, self._rows = (
            runner, dev, None, rows)
        self.steps = None

    @property
    def unread(self):
        return self._host is None

    def __array__(self, dtype=None, copy=None):
        if self._host is None:
            with _obs.trace_span("runner.wait"):
                host = np.asarray(self._dev)
            if self._runner._counts_seen.size:
                host = self._runner._strip_counts(host, self._rows)
            Q = self._runner.plan.block
            if Q:
                host, self.steps = host[:Q], host[Q:]
            self._host, self._dev = host, None
        return self._host if dtype is None else self._host.astype(dtype)


class ModelRunner:
    """Weights + paged KV + jitted forwards over one mesh (slice)."""

    def __init__(self, model, mesh=None, mp_axis="mp", pp_axis="pp",
                 max_batch=4, page_size=16, prefill_chunk=32, n_pages=None,
                 use_kernel=None, kv_cache_dtype="auto"):
        cfg = model.config
        self.cfg = cfg
        self.mesh = mesh
        self.max_batch = int(max_batch)
        self.page = int(page_size)
        self.chunk = int(prefill_chunk)
        self.n_pages = int(n_pages)
        self.trash_page = self.n_pages - 1
        if use_kernel is None:
            use_kernel = _kernel_applies(self.devices[0], mesh)
        self.use_kernel = use_kernel
        self.plan = plan = model.serving_plan(kernels=bool(use_kernel))

        with _obs.trace_span("engine.build.weights"):
            if mesh is not None:
                axes = {a: (a if a in mesh.axis_names else None)
                        for a in (pp_axis, mp_axis, "ep")}
                specs = plan.specs(axes[pp_axis], axes[mp_axis], axes["ep"])
                cache_spec = NamedSharding(mesh, P(axes[pp_axis]))
            else:
                cache_spec = None
            # host -> mesh directly: jnp.asarray would stage every
            # replica's weights on the default device first. Leaf by leaf:
            # a model that hands its leaves over lets go of each here
            self.W = {}
            for k, v in plan.weights():
                self.W[k] = (jnp.asarray(v) if mesh is None else
                             jax.device_put(v, NamedSharding(mesh, specs[k])))
            dtype = self.W["embed"].dtype
        self.cache_sharding = cache_spec
        self.kv_quant = (kv_cache_dtype == "int8")
        page_dtype = jnp.int8 if self.kv_quant else dtype
        L = plan.layers_of("pages")
        kvh, D = plan.kvh, plan.D

        def pool(dt, *tail):    # born on its own devices (None: the default)
            return jnp.zeros((L, self.n_pages, page_size, kvh) + tail, dt,
                             device=cache_spec)
        with _obs.trace_span("engine.build.pool"):
            self.cache = (pool(page_dtype, D), pool(page_dtype, D))
            if self.kv_quant:
                self.cache += (pool(jnp.float32), pool(jnp.float32))
        if plan.recurrent:
            # a row a slot, and one that idle rows write to; born after the
            # weights are placed, so construction never holds them beside
            # a second copy of anything
            rows = (plan.layers_of("state"), self.max_batch + 1)
            with _obs.trace_span("engine.build.state_pool"):
                self.cache += (
                    jnp.zeros(rows + (plan.state_heads, plan.state_dk,
                                      plan.state_dv), jnp.float32,
                              device=cache_spec),
                    jnp.zeros(rows + (plan.conv_tail, plan.conv_channels),
                              dtype, device=cache_spec))
        if plan.block:
            if self.page % plan.block or self.chunk % plan.block:
                raise ValueError(
                    f"a model that generates by blocks of {plan.block} needs "
                    f"page_size ({self.page}) and prefill_chunk "
                    f"({self.chunk}) to be whole blocks")
            # the block program's own counts (BLOCK_COUNTS), as the routing
            # counts below: summed on the device, read as differences
            self._block_counts_at = len(self.cache)
            self.cache += (jnp.zeros((len(BLOCK_COUNTS),), jnp.int32),)
        if plan.counts:
            # the layers' routing counts summed on the device, a row a kind
            # of dispatch (_COUNT_ROWS); int32 that wraps, read as differences
            self.cache += (jnp.zeros((len(_COUNT_ROWS), plan.counts),
                                     jnp.int32),)
        # what rides home behind a decode dispatch's tokens, flat: the
        # block counts, then the routing counts
        self._counts_seen = np.zeros(
            (len(BLOCK_COUNTS) if plan.block else 0)
            + len(_COUNT_ROWS) * plan.counts, np.int64)
        self._counts_grown = np.zeros_like(self._counts_seen)
        # the last decode dispatch's tokens: the rows' last ones as the next
        # dispatch takes them back on the device (``_build_decode``), and
        # what ``run_decode`` handed the engine, which knows if they were read
        self._last = jnp.zeros(
            (self.max_batch,), jnp.int32,
            device=None if mesh is None else NamedSharding(mesh, P()))
        self._tokens = None
        self._prefill = self._build_prefill()
        self._decode_programs: dict = {}
        self._verify_programs: dict = {}
        self._launched = set()      # programs that have run (and so compiled)
        self._copy_page_fn = None
        self._gather_fn = {}
        self._scatter_fn = {}

    @property
    def devices(self):
        """The device set this runner's buffers live on."""
        if self.mesh is not None:
            return tuple(self.mesh.devices.reshape(-1))
        return (jax.devices()[0],)

    # ---------------------------------------------------------------- layers
    @property
    def _n_page_pools(self):
        """The page pools lead ``self.cache``: K and V, and the two scale
        pools of int8 pages; a recurrent model's state pool and tails
        follow, then the routing counts of a model that has them."""
        return 4 if self.kv_quant else 2

    def _count(self, cache, counts, kind):
        """Add one layer's routing counts (``None``: the layer has none) to
        the sums' row of this kind of dispatch."""
        if counts is None:
            return cache
        row = _COUNT_ROWS.index(kind)
        return cache[:-1] + (cache[-1].at[row].add(counts),)

    def _layer_fns(self, W, r, kind):
        """The scan body of every kind of layer, for one dispatch's rows
        ``r`` (``kind``: decode | prefill | verify): what decode, prefill and
        speculative verification share; they differ only in how many rows
        ride the batch dim and where those rows' pages and states are. A
        kind's ``whole`` leaves join the layer's scanned-in ones unsliced."""
        def with_whole(lk, layer):
            if not lk.whole:
                return layer
            whole = {k: W[k] for k in lk.whole}
            return lambda carry, wl: layer(carry, {**wl, **whole})
        return {name: with_whole(lk, (
            self._pages_layer if lk.cache == "pages"
            else self._state_layer)(lk, r, kind))
            for name, lk in self.plan.kinds.items()}

    def _pages_layer(self, lk, r, kind):
        """A layer that attends. With ``r.mq = (B, Q)`` the flat rows
        are B sequences x Q consecutive query positions and attention goes
        through the multi-query kernel (tables [B, S]; ctx [B] is row 0's
        context length, row j sees ctx+j); KV writes stay per-flat-row.

        The body's carry is ``(x, cache)``: the WHOLE stacked pools ride
        beside the activations and layer ``l`` (scanned in as ``wl["l"]``,
        its index among the layers that attend) scatters its rows into
        them at ``(l, page_idx, within)``. Attention
        then reads the pages as one flat stack of ``L * n_pages`` (a
        reshape of the two leading axes: a bitcast) through the tables
        shifted by ``l * n_pages``; the scale pools of int8 pages, a
        sixteenth of their size, are written the same way and read by the
        layer. A pool that is scanned over instead is sliced out per
        layer, written back per layer and, being donated while still read,
        copied whole once a dispatch.

        ``r.horizon == "block"`` (a block program's rows): the Q rows of a
        sequence are one block and each sees ``ctx + Q - 1`` tokens, the
        block's own, just written, included."""
        nh, D = self.plan.nh, self.plan.D
        use_kernel = self.use_kernel
        quant = self.kv_quant
        n_pages = self.n_pages
        page_idx, within, tables, ctx, pos, mq = (
            r.page_idx, r.within, r.tables, r.ctx, r.pos, r.mq)

        def layer(carry, wl):
            from ...ops.pallas.paged_attention import (
                paged_attention, paged_attention_blocks,
                paged_attention_multiquery, paged_attention_multiquery_ref,
                paged_attention_ref, quantize_kv)
            x, cache = carry
            l = wl["l"]
            q, k, v = lk.first(wl, x, pos)
            if mq is None:
                attn = paged_attention if use_kernel else paged_attention_ref
            else:
                Bq, Q = mq
                # a block's rows see the whole block (its kernel stands in
                # the trace as ``paged_attention``); verification's rows
                # their own horizon, the default
                base, hz = (paged_attention_multiquery if use_kernel
                            else paged_attention_multiquery_ref), {}
                if getattr(r, "horizon", None) == "block":
                    if use_kernel:
                        base = paged_attention_blocks
                    else:
                        hz = {"horizon": "block"}

                def attn(qx, kp, vp, tb, cl, **kw):
                    out = base(qx.reshape(Bq, Q, nh, D), kp, vp, tb, cl,
                               **kw, **hz)
                    return out.reshape(Bq * Q, nh, D)
            rows = (k, v)
            if quant:                       # int8 pages + their scale pools
                kq, ksc = quantize_kv(k)
                vq, vsc = quantize_kv(v)
                rows = (kq, vq, ksc, vsc)
            # write first, read after: the pool has one live value
            pages = tuple(a.at[l, page_idx, within].set(rw)
                          for a, rw in zip(cache, rows))
            cache = pages + cache[len(pages):]
            kp, vp = (a.reshape((-1,) + a.shape[2:]) for a in cache[:2])
            # the scales go in by the layer, under the tables as they came:
            # the TPU keeps a pool whose last axis is KVH in a layout of
            # its own, and the kernel's would cost a copy of all of it
            kw = ({"k_scales": cache[2][l], "v_scales": cache[3][l],
                   "scale_tables": tables} if quant else {})
            att = attn(q, kp, vp, tables + l * n_pages, ctx, **kw)
            x, counts = lk.second(wl, x, att, r.live)
            return (x, self._count(cache, counts, kind)), None

        return layer

    def _state_layer(self, lk, r, kind):
        """A layer with a recurrence: its state ``[heads, dk, dv]`` and the
        tail of its convolutions live in the two pools after the pages, at
        ``(l, slot)``; ``l`` is the layer's index among its like.

        A DECODE row is its slot's next token (``r.slots [B]``; an idle row
        names the idle slot): the tails are gathered and scattered back (a
        few MB), the states are not - ``kda_step`` updates the rows it is
        told in place, in the pool flattened to ``[L * slots, ...]``. A
        PREFILL chunk is ``r.n_valid`` consecutive positions of ONE slot:
        its state and tail are sliced out, run through the chunk and
        written back, and where the chunk starts the prompt (``r.start ==
        0``) both begin at zero whatever the slot held - so admission,
        and re-admission after a preemption, need no pass of their own.
        The chunk's padding rows leave the state as it was."""
        from ...ops.pallas.kda import kda_recurrence, kda_step, kda_step_ref
        if kind == "verify":
            raise NotImplementedError(
                "speculative verification over recurrent state: rejected "
                "drafts would need the state rolled back")
        at = self._n_page_pools
        step = kda_step if self.use_kernel else kda_step_ref

        def layer(carry, wl):
            x, cache = carry
            l = wl["l"]
            state, conv = cache[at], cache[at + 1]
            if kind == "decode":
                (q, k, v, g, beta), tails = lk.first(
                    wl, x[:, None], conv[l, r.slots], None)
                conv = conv.at[l, r.slots].set(tails)
                flat = state.reshape((-1,) + state.shape[2:])
                o, flat = step(flat, l * state.shape[1] + r.slots, q[:, 0],
                               k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                state = flat.reshape(state.shape)
            else:
                fresh = r.start == 0
                (q, k, v, g, beta), tail = lk.first(
                    wl, x, jnp.where(fresh, 0, conv[l, r.slot]), r.n_valid)
                live = r.live
                o, S = kda_recurrence(
                    jnp.where(fresh, 0.0, state[l, r.slot]), q, k, v,
                    jnp.where(live[:, None, None], g, 0.0),
                    jnp.where(live[:, None], beta, 0.0))
                state = state.at[l, r.slot].set(S)
                conv = conv.at[l, r.slot].set(tail)
            cache = cache[:at] + (state, conv) + cache[at + 2:]
            x, counts = lk.second(wl, x, o, r.live)
            return (x, self._count(cache, counts, kind)), None

        return layer

    def _run_layers(self, W, cache, x, fns):
        """Run the model's layers: inside a period one ``lax.scan`` for
        each run of like layers, over that kind's stacked leaves and the
        layers' indices among their like (``wl["l"]``), and one scan over
        the periods around them (none where the leaves have no period
        axis). Only weights and indices are scanned; the pools are carried
        and come back updated in place (see :meth:`_pages_layer` for the
        copies that avoids)."""
        plan = self.plan

        def period(carry, Wp, p):
            for name, n in plan.period:
                lk = plan.kinds[name]
                per_layer = {k: Wp[k] for k in lk.keys if k not in lk.whole}
                l = jnp.arange(n, dtype=jnp.int32)
                per_layer["l"] = l if p is None else l + p * n
                carry, _ = jax.lax.scan(fns[name], carry, per_layer)
            return carry

        if plan.periods is None:
            return period((x, cache), W, None)
        leaves = {k: W[k] for name, _ in plan.period
                  for k in plan.kinds[name].keys
                  if k not in plan.kinds[name].whole}
        carry, _ = jax.lax.scan(
            lambda carry, wp: (period(carry, *wp), None), (x, cache),
            (leaves, jnp.arange(plan.periods, dtype=jnp.int32)))
        return carry

    # ------------------------------------------------------------- programs
    def _build_decode(self, K):
        """K decode steps fused into ONE dispatch (token feedback stays
        in-graph via lax.scan) — every dispatch costs a fixed host latency,
        which a per-token loop pays in full; a K-block pays 1/K of it per
        token. The host sees the K sampled tokens afterwards, so eos
        requests cap K at 1 (every token must be inspected). Mirrors
        generate()'s tokens_per_dispatch.

        The feedback goes on ACROSS dispatches too: the program hands back,
        beside the K tokens a row, each row's last one ``[B]`` as a value of
        its own, and takes the dispatch before's as ``prev`` with a mask
        ``take [B]``. A row whose mask is set decodes from ``prev`` — the
        device's own token, which the host may not have read yet — and
        every other row from the host's ``tokens`` (a row fresh from
        prefill; every row when nothing is in flight). So the engine can
        launch step N+1 while step N's tokens are still on their way home
        (``LLMEngine.step``). One program a K whatever the dispatch before
        was: ``prev`` has one shape."""
        if self.plan.block:
            if K != self.plan.block:
                raise ValueError(
                    f"this model generates by blocks of {self.plan.block}: "
                    f"a decode dispatch is one block, not {K} steps")
            return self._build_block()
        page = self.page
        eps = self.cfg.rms_norm_eps
        trash = self.trash_page

        def block(W, cache, tokens, lens, tables, active,
                  greedy, temp, topp, topk, seeds, fold, take, prev):
            # tokens [B] int32; lens [B] tokens already cached; tables
            # [B, S] page ids; active [B] 0/1; sampling params [B].
            # fold [B]: 1 -> vary the sampling key per block step (seedless
            # requests); 0 -> reuse it (fixed-seed generate parity).
            # take [B]: 1 -> the row's token is prev's, not tokens'.
            tokens = jnp.where(take > 0, prev, tokens)

            def one(carry, i):
                tokens, lens, cache = carry
                x = W["embed"][tokens]                   # [B, H]
                pos = lens.astype(jnp.int32)
                page_idx = jnp.take_along_axis(
                    tables, (pos // page)[:, None], axis=1)[:, 0]
                # inactive slots write into the trash page, never a live one
                page_idx = jnp.where(active > 0, page_idx, trash)
                within = pos % page
                ctx = jnp.where(active > 0, pos + 1, 1).astype(jnp.int32)
                rows = types.SimpleNamespace(
                    page_idx=page_idx, within=within, tables=tables, ctx=ctx,
                    pos=pos, mq=None, live=active)
                if self.plan.recurrent:     # idle rows: the idle slot
                    B = tokens.shape[0]
                    rows.slots = jnp.where(
                        active > 0, jnp.arange(B, dtype=jnp.int32), B)
                x, cache = self._run_layers(
                    W, cache, x, self._layer_fns(W, rows, "decode"))
                h = rms_norm(x, W["norm"], eps)
                logits = h.astype(jnp.float32) @ W["head"].astype(
                    jnp.float32)
                nxt = _sample_rows(logits, greedy, temp, topp, topk,
                                   seeds + i * fold)
                tokens = jnp.where(active > 0, nxt, tokens)
                lens = lens + (active > 0).astype(lens.dtype)
                return (tokens, lens, cache), nxt

            (last, _, cache2), toks = jax.lax.scan(
                one, (tokens, lens, cache),
                jnp.arange(K, dtype=jnp.int32))
            if self.plan.counts:
                # the sums ride home behind the tokens, in the one array the
                # host fetches anyway: [K, B + kinds * counts]
                toks = jnp.concatenate(
                    [toks, jnp.broadcast_to(cache2[-1].reshape(1, -1),
                                            (K, cache2[-1].size))], axis=1)
            if self.mesh is not None:
                # the next dispatch's ``prev``: held to the placement the
                # first one's was given, so a K compiles once
                last = jax.lax.with_sharding_constraint(
                    last, NamedSharding(self.mesh, P()))
            return (toks, last), cache2                  # toks [K, B]

        return jax.jit(block, donate_argnums=(1,))

    def _build_block(self):
        """The decode program of a model that generates by blocks
        (``plan.block`` = Q): ONE BLOCK of Q positions a live sequence a
        dispatch, under the name and the call of the K-step program.

        ``tokens [B, Q]`` is the block as the host knows it: a token where
        one is known (the prompt's last ``len % Q``, in a request's first
        block), -1 where one is to be generated, -2 where the position is
        past the request's budget (its last block): that one stays
        ``[MASK]`` through every step and yields nothing. What is masked is
        kept as a mask of its own from there on, never read off the ids.
        ``lens [B]`` is where the block starts: the tokens committed before
        it, whole blocks.

        Up to ``plan.denoising_steps`` forwards of the ``B x Q`` rows
        (``mq = (B, Q)``, the block's horizon: every row attends to the
        committed prefix and to the WHOLE block), the masked positions fed
        ``plan.mask_token``; each writes the block's K and V into its pages
        before it attends, runs the head on all rows, samples a token and
        its confidence at every position (the logits AT a position are that
        position's), and ``plan.unmask`` says which masked positions keep
        theirs. A ``lax.scan`` where the rule takes a fixed number of
        positions a step; a ``lax.while_loop`` on "any live row still
        masked" where it may finish early (``plan.early_exit``). Then one
        forward of the finished block, no head: the K and V it writes are
        what later blocks read.

        Returns ``(out [2Q, B + counts], prev)``: the block's tokens ``[Q,
        B]`` over the step each was unmasked at (-1: it came known), and
        behind them the block counts (``BLOCK_COUNTS``) and the layers'
        routing sums. A new block starts from ``[MASK]`` rows and needs no
        token of the block before it: ``take`` and ``prev`` carry nothing
        here and ``prev`` goes back as it came."""
        plan = self.plan
        Q, D = plan.block, plan.denoising_steps
        page = self.page
        eps = self.cfg.rms_norm_eps
        trash = self.trash_page
        B = self.max_batch
        at = self._block_counts_at

        def block(W, cache, tokens, lens, tables, active,
                  greedy, temp, topp, topk, seeds, fold, take, prev):
            live = active > 0
            row_j = jnp.tile(jnp.arange(Q, dtype=jnp.int32), B)  # [B*Q]

            def rep(a):
                return jnp.repeat(a, Q)

            pos = rep(lens.astype(jnp.int32)) + row_j
            page_idx = jnp.take_along_axis(
                tables, (pos // page).reshape(B, Q), axis=1).reshape(-1)
            # inactive slots write into the trash page, never a live one
            page_idx = jnp.where(rep(live), page_idx, trash)
            rows = types.SimpleNamespace(
                page_idx=page_idx, within=pos % page, tables=tables,
                ctx=jnp.where(live, lens + 1, 1).astype(jnp.int32), pos=pos,
                mq=(B, Q), live=rep(active), horizon="block")
            sample = [rep(a) for a in (greedy, temp, topp, topk)]

            def forward(cache, toks):
                return self._run_layers(
                    W, cache, W["embed"][toks.reshape(-1)],
                    self._layer_fns(W, rows, "decode"))

            def denoise(carry, s):
                toks, masked, steps, cache = carry
                x, cache = forward(
                    cache, jnp.where(masked | cut, plan.mask_token, toks))
                h = rms_norm(x, W["norm"], eps)
                logits = h.astype(jnp.float32) @ W["head"].astype(
                    jnp.float32)
                x0, conf = _sample_rows_conf(
                    logits, *sample, rep(seeds) + (s * Q + row_j) * rep(fold))
                keep = masked & plan.unmask(conf.reshape(B, Q), masked, s)
                return (jnp.where(keep, x0.reshape(B, Q), toks),
                        masked & ~keep, jnp.where(keep, s, steps), cache)

            cut = tokens == -2
            start = (jnp.maximum(tokens, 0), (tokens == -1) & live[:, None],
                     jnp.full((B, Q), -1, jnp.int32), cache)
            if plan.early_exit:
                n, (toks, _, steps, cache) = jax.lax.while_loop(
                    lambda c: (c[0] < D) & jnp.any(c[1][1]),
                    lambda c: (c[0] + 1, denoise(c[1], c[0])),
                    (jnp.int32(0), start))
            else:
                (toks, _, steps, cache), _ = jax.lax.scan(
                    lambda c, s: (denoise(c, s), None), start,
                    jnp.arange(D, dtype=jnp.int32))
                n = jnp.int32(D)
            _, cache = forward(                         # commit: no head
                cache, jnp.where(cut, plan.mask_token, toks))
            grown = jnp.stack([n, jnp.int32(1),
                               (n + 1) * jnp.sum(live, dtype=jnp.int32)])
            cache = cache[:at] + (cache[at] + grown,) + cache[at + 1:]
            out = jnp.concatenate([toks.T, steps.T])            # [2Q, B]
            tail = jnp.concatenate([c.reshape(-1) for c in cache[at:]])
            out = jnp.concatenate(
                [out, jnp.broadcast_to(tail[None], (2 * Q, tail.size))],
                axis=1)
            if self.mesh is not None:
                prev = jax.lax.with_sharding_constraint(
                    prev, NamedSharding(self.mesh, P()))
            return (out, prev), cache

        return jax.jit(block, donate_argnums=(1,))

    def _build_prefill(self):
        page = self.page
        eps = self.cfg.rms_norm_eps
        trash = self.trash_page
        C = self.chunk
        Q = self.plan.block

        def prefill(W, cache, tokens, start, table, n_valid,
                    greedy, temp, topp, topk, seed, slot=None):
            # tokens [C] int32 (one slot's prompt chunk, zero-padded);
            # start scalar; table [S]; n_valid scalar <= C. Chunk rows ride
            # the paged-attention BATCH dim: row i gets ctx = start+i+1, so
            # in-chunk causality and attention to the already-cached prefix
            # both fall out of the per-row context length. A model that
            # generates by blocks of Q: a row sees to the end of its own
            # block (the chunk's K and V are all written before any row
            # attends), never past the chunk, which holds whole blocks.
            x = W["embed"][tokens]                       # [C, H]
            offs = jnp.arange(C, dtype=jnp.int32)
            pos = start.astype(jnp.int32) + offs
            valid = offs < n_valid
            page_idx = table[pos // page]
            page_idx = jnp.where(valid, page_idx, trash)
            within = pos % page
            if Q:       # causal from block to block, open inside a block
                seen = jnp.minimum((pos // Q + 1) * Q,
                                   start.astype(jnp.int32) + n_valid)
            else:
                seen = pos + 1
            ctx = jnp.where(valid, seen, 1).astype(jnp.int32)
            tables = jnp.broadcast_to(table[None, :], (C, table.shape[0]))
            rows = types.SimpleNamespace(
                page_idx=page_idx, within=within, tables=tables, ctx=ctx,
                pos=pos, mq=None, live=valid, slot=slot, start=start,
                n_valid=n_valid)
            x, cache2 = self._run_layers(
                W, cache, x, self._layer_fns(W, rows, "prefill"))
            if Q:       # a block model's chunk emits no token: no head
                return jnp.int32(0), cache2
            h = rms_norm(x, W["norm"], eps)
            last = h[jnp.maximum(n_valid - 1, 0)]
            logits = last.astype(jnp.float32) @ W["head"].astype(jnp.float32)
            nxt = _sample_rows(logits[None], greedy[None], temp[None],
                               topp[None], topk[None], seed[None])[0]
            return nxt, cache2

        return jax.jit(prefill, donate_argnums=(1,))

    def _build_verify(self, Kv):
        """ONE forward scoring Kv consecutive positions per request — the
        speculative-decoding verifier. Row 0 carries the pending token
        (what plain decode would feed), rows 1..n the proposed drafts;
        sampling row j yields the target model's token AFTER draft j, so
        the host accepts the longest draft prefix matching the sampled
        tokens and emits accepted+1 tokens from a single dispatch. All Kv
        KV writes land in-graph; the host rolls back pages past the
        accepted point afterwards (attention masks by context length, so
        stale writes beyond a slot's length are never attended)."""
        page = self.page
        eps = self.cfg.rms_norm_eps
        trash = self.trash_page
        B = self.max_batch

        def verify(W, cache, tokens, lens, tables, n_rows,
                   greedy, temp, topp, topk, seeds, fold):
            # tokens [B, Kv] int32 (row 0 = pending, 1.. = drafts, rest
            # padding); lens [B] tokens already cached; n_rows [B] valid
            # rows (0 = inactive slot); sampling params [B] as in decode.
            row_j = jnp.tile(jnp.arange(Kv, dtype=jnp.int32), B)  # [B*Kv]

            def rep(a):
                return jnp.repeat(a, Kv)

            pos = rep(lens.astype(jnp.int32)) + row_j
            valid = row_j < rep(n_rows)
            page_idx = jnp.take_along_axis(
                tables, (pos // page).reshape(B, Kv), axis=1).reshape(-1)
            page_idx = jnp.where(valid, page_idx, trash)
            within = pos % page
            # row 0 of an active request sees lens+1 tokens (its own write
            # included); the multi-query kernel extends by +j per row
            cl = jnp.where(n_rows > 0, lens + 1, 1).astype(jnp.int32)
            x = W["embed"][tokens.reshape(-1)]            # [B*Kv, H]
            rows = types.SimpleNamespace(
                page_idx=page_idx, within=within, tables=tables, ctx=cl,
                pos=pos, mq=(B, Kv), live=valid)
            x, cache2 = self._run_layers(
                W, cache, x, self._layer_fns(W, rows, "verify"))
            h = rms_norm(x, W["norm"], eps)
            logits = h.astype(jnp.float32) @ W["head"].astype(jnp.float32)
            # seed schedule mirrors the decode block's `seeds + i*fold`:
            # emitted token #j of this step draws the key step #j of a
            # non-speculative block would have drawn, so fixed-seed
            # (fold=0) and greedy requests stay token-exact vs spec-off
            seeds_rep = rep(seeds) + row_j * rep(fold)
            toks = _sample_rows(logits, rep(greedy), rep(temp), rep(topp),
                                rep(topk), seeds_rep)
            return toks.reshape(B, Kv), cache2

        return jax.jit(verify, donate_argnums=(1,))

    # ------------------------------------------------------------- dispatch
    def has_decode_program(self, k):
        return k in self._decode_programs

    def has_verify_program(self, kv):
        return kv in self._verify_programs

    def _launch(self, key, prog, attrs, *args):
        """Hand one dispatch's host values to the device
        (``runner.dispatch``) and launch ``prog`` on them
        (``runner.launch``: the jitted call until it returns, which on the
        chip is not at once — a prefill chunk's call sits out the chunk
        before it); returns the program's first output as a device value.
        A program's first launch traces, lowers and compiles it: that one
        goes whole under ``engine.build.programs``, so the two leaf spans
        hold the steady state alone."""
        if key not in self._launched:
            with _obs.trace_span("engine.build.programs", **attrs):
                out, self.cache = prog(self.W, self.cache,
                                       *[jnp.asarray(a) for a in args])
            self._launched.add(key)
            return out
        with _obs.trace_span("runner.dispatch", **attrs):
            args = [jnp.asarray(a) for a in args]
        with _obs.trace_span("runner.launch", kind=key[0]):
            out, self.cache = prog(self.W, self.cache, *args)
        return out

    def run_prefill(self, tokens, start, table, n_valid,
                    greedy, temp, topp, topk, seed, slot=0):
        """Dispatch one prefill chunk; returns the sampled next token as a
        DEVICE value (only the caller decides whether to sync on it — a
        mid-prompt chunk's sample is never read). ``slot``: whose state the
        chunk continues, for a model that keeps recurrent state (one that
        keeps none is not told)."""
        attrs = ({"kind": "prefill", "rows": int(n_valid), "start": int(start),
                  "argmax_only": _argmax_only(greedy)}
                 if _obs.enabled() else {})
        state = (np.int32(slot),) if self.plan.recurrent else ()
        if state and attrs:
            attrs["state_rows"] = 1
        return self._launch(
            ("prefill",), self._prefill, attrs, tokens, np.int32(start), table,
            np.int32(n_valid), np.int32(greedy), np.float32(temp),
            np.float32(topp), np.int32(topk), np.int32(seed), *state)

    def run_decode(self, k, tokens, lens, tables, active,
                   greedy, temp, topp, topk, seeds, fold, take=None):
        """Launch one K-token decode block and return WITHOUT waiting for
        it, as ``run_prefill`` does: what comes back is a
        :class:`_DecodeTokens`, which answers ``np.asarray`` with host
        tokens [k, B] — waiting for them under ``runner.wait`` the first
        time it is asked. The copy home starts here.

        ``take`` [B] (none: no row): the rows that decode from the token
        the dispatch before left ON THE DEVICE, whether or not the host has
        read it; the others decode from ``tokens``. Every argument is a
        HOST array, and the dispatch's own from here on: their way to the
        device may outlast the call, so the caller hands over copies of
        what it goes on to change."""
        prog = self._decode_programs.get(k)
        if prog is None:
            prog = self._decode_programs[k] = self._build_decode(k)
        B = len(tokens)
        if take is None:
            take = np.zeros((B,), np.int32)
        attrs = {}
        if _obs.enabled():
            # what a roofline needs of this dispatch: the rows that decode
            # and the valid context each of them reads; ahead: launched
            # while the dispatch before's tokens were unread
            ctx = (np.asarray(lens) + 1)[np.asarray(active) > 0]
            attrs = {"kind": "decode", "rows": int(ctx.size),
                     "ctx_sum": int(ctx.sum()), "k": int(k),
                     "argmax_only": _argmax_only(greedy),
                     "ahead": int(self.decode_unread)}
            if self.plan.recurrent:
                attrs["state_rows"] = int(ctx.size)
            if self.plan.block:     # the most forwards the dispatch makes
                attrs.update(block=self.plan.block,
                             forwards=self.plan.denoising_steps + 1)
        toks, self._last = self._launch(
            ("decode", k), prog, attrs, tokens, lens, tables, active, greedy,
            temp, topp, topk, seeds, fold, take, self._last)
        toks.copy_to_host_async()
        self._tokens = _DecodeTokens(self, toks, B)
        return self._tokens

    @property
    def decode_unread(self):
        """Whether the last decode dispatch's tokens are still unread."""
        return self._tokens is not None and self._tokens.unread

    def _strip_counts(self, toks, B):
        """Take the routing sums off the back of a decode block's tokens
        (the last step's are the block's) and keep what they grew by,
        modulo the device's 32 bits - the chunks' since the block before
        included - for :meth:`take_routing_counts`."""
        seen = toks[-1, B:].astype(np.int64)
        self._counts_grown += (seen - self._counts_seen) % (1 << 32)
        self._counts_seen = seen
        return toks[:, :B]

    def take_routing_counts(self):
        """What the layers' routing counts grew by since the last call:
        ``[kind of dispatch (decode, prefill), count]`` int64, empty for a
        model that has none."""
        n = len(_COUNT_ROWS) * self.plan.counts
        at = self._counts_grown.size - n
        grown = self._counts_grown[at:].reshape(len(_COUNT_ROWS), -1).copy()
        self._counts_grown[at:] = 0
        return grown

    def take_block_counts(self):
        """What the block program's own counts (``BLOCK_COUNTS``) grew by
        since the last call; empty for a model that generates a token a
        step."""
        n = len(BLOCK_COUNTS) if self.plan.block else 0
        grown = self._counts_grown[:n].copy()
        self._counts_grown[:n] = 0
        return grown

    def run_verify(self, kv, tokens, lens, tables, n_rows,
                   greedy, temp, topp, topk, seeds, fold):
        """Dispatch one speculative verify step; returns host tokens
        [B, Kv]."""
        prog = self._verify_programs.get(kv)
        if prog is None:
            prog = self._verify_programs[kv] = self._build_verify(kv)
        attrs = {}
        if _obs.enabled():
            # row j of a slot reads lens + 1 + j tokens of context
            n = np.asarray(n_rows, np.int64)
            ctx_sum = int((n * (np.asarray(lens) + 1) + n * (n - 1) // 2).sum())
            attrs = {"kind": "verify", "rows": int(n.sum()),
                     "ctx_sum": ctx_sum, "k": int(kv),
                     "argmax_only": _argmax_only(greedy)}
        toks = self._launch(("verify", kv), prog, attrs, tokens, lens, tables,
                            n_rows, greedy, temp, topp, topk, seeds, fold)
        with _obs.trace_span("runner.wait"):
            return np.asarray(toks)

    # ---------------------------------------------------------- page movement
    def copy_page(self, src, dst):
        """Device-side copy of one physical KV page (all layers, K and V,
        int8 scales included) — the copy half of copy-on-write."""
        if self._copy_page_fn is None:
            def cp(cache, s, d):
                return tuple(a.at[:, d].set(a[:, s]) for a in cache)
            self._copy_page_fn = jax.jit(cp, donate_argnums=(0,))
        n = self._n_page_pools
        self.cache = self._copy_page_fn(
            self.cache[:n], jnp.asarray(np.int32(src)),
            jnp.asarray(np.int32(dst))) + self.cache[n:]

    def gather_pages(self, page_idx):
        """Pull ``page_idx`` pages out of the cache as a dense block (tuple
        of [L, n, page, ...] arrays) — the send half of a cross-slice KV
        handoff.  The gather is jitted per block size so repeated handoffs
        at one size reuse the program."""
        n = len(page_idx)
        fn = self._gather_fn.get(n)
        if fn is None:
            def gather(cache, idx):
                return tuple(a[:, idx] for a in cache)
            fn = self._gather_fn[n] = jax.jit(gather)
        return fn(self.cache[:self._n_page_pools],
                  jnp.asarray(np.asarray(page_idx, np.int32)))

    def scatter_pages(self, page_idx, block):
        """Write a dense page block into ``page_idx`` of this runner's cache
        — the receive half of a cross-slice KV handoff.  The cache buffers
        are donated, so the write is in-place where XLA allows."""
        n = len(page_idx)
        fn = self._scatter_fn.get(n)
        if fn is None:
            def scatter(cache, blk, idx):
                return tuple(a.at[:, idx].set(b) for a, b in zip(cache, blk))
            fn = self._scatter_fn[n] = jax.jit(scatter, donate_argnums=(0,))
        at = self._n_page_pools
        self.cache = fn(self.cache[:at], block, jnp.asarray(
            np.asarray(page_idx, np.int32))) + self.cache[at:]

    def kv_bytes_per_page(self):
        """HBM bytes one KV page costs across all layers (both K and V,
        including int8 scales) — the unit of the page_pool budget."""
        return sum(int(a.nbytes) for a in
                   self.cache[:self._n_page_pools]) // self.n_pages

    def state_bytes_per_slot(self):
        """HBM bytes one slot's recurrent state costs across all layers
        (state and convolution tails; 0 for a model that keeps none): what
        a slot holds whatever its length, beside its pages."""
        if not self.plan.recurrent:
            return 0
        at = self._n_page_pools
        return sum(int(a.nbytes) for a in
                   self.cache[at:at + 2]) // (self.max_batch + 1)

    def pages_to_host(self, page_idx):
        """Gather ``page_idx`` pages and land them in host RAM as a tuple of
        owned numpy arrays (one [L, n, page, ...] array per cache component)
        — the device half of a host-tier spill.  Uses the checkpoint
        snapshot idiom: start the non-blocking device→host DMA first, then
        materialize owned copies (np.array, never a view) so the block
        outlives any later donation of the cache buffers."""
        blk = self.gather_pages(page_idx)
        for a in blk:
            try:
                a.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass                      # older arrays: np.array blocks
        return tuple(np.array(a) for a in blk)

    def put_block(self, block):
        """Start the transfer of a gathered page block (device arrays from
        another runner's ``gather_pages``, or host numpy arrays off the
        RPC plane) onto THIS runner's cache sharding.  ``device_put`` is
        asynchronous — the returned arrays are in flight and a subsequent
        ``scatter_pages`` chains on them, so the copy overlaps whatever
        the caller dispatches in between."""
        dst = self.cache_sharding if self.cache_sharding is not None \
            else self.devices[0]
        return tuple(jax.device_put(a, dst) for a in block)

    def restore_pages(self, page_idx, host_blocks):
        """Write host-tier page blocks back into device pages ``page_idx``
        (one single-page block per entry, in order) — the device half of a
        spill restore.  Double-buffered: page i+1's host→device transfer is
        issued before page i's scatter is dispatched, so the copy hides
        behind the previous write."""
        if not page_idx:
            return
        pending = jax.device_put(host_blocks[0])
        for i, p in enumerate(page_idx):
            blk, pending = pending, (
                jax.device_put(host_blocks[i + 1])
                if i + 1 < len(page_idx) else None)
            self.scatter_pages([p], blk)
