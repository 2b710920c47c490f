"""The decoder scaffold: what every per-machine sequence model over sensor
values read as tokens shares, whatever its attention and its router.

No reference counterpart. Each tag is a sequence of its own, as the
``patchtst`` kind is channel-independent: a scaled value in [0, 1] is binned
into ``vocab_size`` ids, the model predicts the NEXT row's bin at every
position, and the module's output is the expected bin centre, so residuals,
error scaler and thresholds stay the stock ones.

:class:`TokenDecoder` holds the token front and back end (``_bins``,
``_sequences``, ``_over_vocabulary``, the expected bin centre), the blocks'
bounds on memory (``_per_sequence``, ``_recomputed``), one layer (attention,
then a dense feed-forward or the expert layer, a shared expert where the
layer's parameters hold one, each sub-block's output normed where they hold
a norm for it) and the next row's loss. A kind brings its
attention block (``_attention``), its scoring function (``_route``) and
its stacks of layers (``setup``, ``_trunk``): ``moe_mla.py`` (latent
attention, sigmoid scores with a selection bias, leading dense layers, a
shared expert, a prediction module), ``moe_gqa.py`` (grouped-query
attention with sliding-window and full layers mixed by period, softmax
scores, none of the three) and ``afmoe.py`` (``moe_gqa.py``'s attention with
an output gate and per-head query/key norms, rotary in the window layers
alone, sandwich norms, sigmoid scores with a selection bias, leading dense
layers, a shared expert).

**The expert layer's contract.** The layer is told which experts it holds
(``experts_held``, ids among ``n_routed_experts``: one chip's share when a
layer is divided over chips; all of them by default). It routes over ALL
experts (:func:`route`, by the published ``scoring_func``, in float32) and
adds only what its own experts give: the partial result goes on, and on one
chip there is no exchange. No token is dropped: the (token, choice) slots
are sorted by held expert, the slots of experts held elsewhere last, and one
grouped product (``jax.lax.ragged_dot``) a weight matrix runs over the
sorted rows, a chunk at a time and only as far as slots of held experts
reach.

The module brings its own loss (``sample_losses``), which
``models.train.make_loss_fn`` calls for the loss named ``"module"``, and
counts the token-slots each held expert received (``expert_tokens``).

A parameter tree is drawn group by group in ONE declared order (Flax folds a
name's position into its key); layers of a kind are stacked on a leading
axis (layer ``i`` from ``split(key, n)[i]``: a trunk scans over them, so the
compiler sees a layer body once); inside a group leaf ``j`` of the sorted
names comes from ``fold_in(key, j)``: normal(0.02) a leading index at a time
(index ``i`` from ``split(key, n)[i]``), norms one, a selection bias zero.
The benchmark's plain references draw the same trees the same way.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

_INIT_STD = 0.02
def _draw_matrix(key, shape: Tuple[int, ...]):
    """normal(0.02), a leading index at a time (index ``i`` from ``split(key,
    n)[i]``): a leaf of gigabytes is drawn without random bits of its size
    beside it."""
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(
        lambda k: _INIT_STD * jax.random.normal(k, shape[1:], jnp.float32), keys
    )


def _draw_stack(key, shapes: Dict[str, Tuple[int, ...]], n: int):
    """``n`` layers of one kind, stacked on a leading axis (layer ``i`` from
    ``split(key, n)[i]``): the trunk scans over them, so a layer is traced,
    compiled and kept as code once however many there are."""
    return jax.lax.map(lambda k: _draw_group(k, shapes), jax.random.split(key, n))


def _draw_group(key, shapes: Dict[str, Tuple[int, ...]]):
    out = {}
    for j, (name, shape) in enumerate(sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = _draw_matrix(jax.random.fold_in(key, j), shape)
    return out


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight).astype(x.dtype)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, router_bias, k: int, scaling: float,
          scoring_func: str = "sigmoid"):
    """``(chosen (T, k), weights (T, k))`` over ALL experts, as published:
    scores (``scoring_func``: ``sigmoid`` or ``softmax`` of the router's
    logits) in float32 at ``highest`` whatever the model computes in; the
    ``k`` largest of the scores plus the selection bias (``None``: the
    router has none); weights the chosen scores over their sum (for
    ``softmax`` that is a softmax over the chosen logits), times
    ``scaling``."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(
            f"Unknown scoring_func {scoring_func!r}; use 'sigmoid' or 'softmax'"
        )
    biased = scores if router_bias is None else scores + jax.lax.stop_gradient(router_bias)
    _, chosen = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scaling


def grouped_experts(x, chosen, weights, held: Sequence[int], n_experts: int,
                    gate, up, down):
    """What the held experts give for tokens ``x (T, D)``: ``(partial (T, D),
    token-slots a held expert (E,))``. The ``T·k`` (token, choice) slots are
    sorted by held expert (a stable sort), those of experts held elsewhere
    last, outside every group, and the grouped products (``ragged_dot``)
    run over the sorted rows a chunk at a time: a chunk is twice what a
    uniform router sends here, so one chunk is the usual case; chunks past
    the last slot of a held expert are skipped, and however many slots fall
    here, none is dropped. (Under ``vmap`` the skip becomes a select: every
    chunk is computed. A slice of one machine is not vmapped.)

    The backward pass is written out (``custom_vjp``): it walks the same
    chunks, makes a chunk's rows and activations again and skips the same
    ones. Left to the transposition of the loop, every chunk, skipped or not,
    would keep the tokens and the three weight matrices it was handed: as
    many copies of them as there are chunks."""
    n_tokens, k = chosen.shape
    n_held, n_slots = len(held), n_tokens * k
    # expert id -> its place among the held ones, or n_held: held elsewhere
    place = np.full((n_experts,), n_held, np.int32)
    place[list(held)] = np.arange(n_held, dtype=np.int32)
    slot_place = jnp.asarray(place)[chosen.reshape(-1)]  # (T * k,)
    chunk = min(n_slots, -(-2 * n_slots * n_held // n_experts // 8) * 8)
    n_chunks = -(-n_slots // chunk)
    firsts = chunk * np.arange(n_chunks, dtype=np.int32)
    order = jnp.pad(
        jnp.argsort(slot_place, stable=True), (0, n_chunks * chunk - n_slots)
    )
    sizes = jnp.bincount(slot_place, length=n_held + 1)[:n_held].astype(jnp.int32)

    def given_by(x, gate, up, down, slot_weights, order, sizes, first):
        """What the chunk of sorted slots that starts at ``first`` gives, a
        row a slot."""
        ends = jnp.cumsum(sizes)
        slots = jax.lax.dynamic_slice(order, (first,), (chunk,))
        # rows past the last group belong to no expert here
        here = (first + jnp.arange(chunk) < ends[-1])[:, None]
        # the part of each expert's group that lies in this chunk
        inside = jnp.clip(ends - first, 0, chunk) - jnp.clip(ends - sizes - first, 0, chunk)

        def grouped(rows, weights):
            # ragged_dot leaves the rows outside every group unwritten
            # (whatever the buffer held, on the TPU): they are zeroed going
            # in and coming out, so neither they nor, in the backward pass,
            # their gradients reach a token
            rows = jnp.where(here, rows, 0.0)
            return jnp.where(here, jax.lax.ragged_dot(rows, weights, inside), 0.0)

        rows = x[slots // k]  # sorted by held expert
        with jax.named_scope("expert_ffn"):
            hidden = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
            given = grouped(hidden, down)
        return given * slot_weights[slots][:, None]

    def over_chunks(start, step, order, sizes):
        """``step(carry, first)`` over the chunks that hold a slot of a held
        expert; the others leave ``carry`` as it is."""
        n_here = jnp.sum(sizes)
        return jax.lax.scan(
            lambda carry, first: (
                jax.lax.cond(first < n_here, step, lambda c, _: c, carry, first),
                None,
            ),
            start, firsts,
        )[0]

    @jax.custom_vjp
    def partial_result(x, gate, up, down, slot_weights, order, sizes):
        def add(partial, first):
            slots = jax.lax.dynamic_slice(order, (first,), (chunk,))
            return partial.at[slots // k].add(
                given_by(x, gate, up, down, slot_weights, order, sizes, first)
            )

        return over_chunks(jnp.zeros_like(x), add, order, sizes)

    def forward(*args):
        return partial_result(*args), args

    def backward(args, g):
        floats, (order, sizes) = args[:5], args[5:]

        def add(grads, first):
            slots = jax.lax.dynamic_slice(order, (first,), (chunk,))
            _, pull = jax.vjp(
                lambda *floats: given_by(*floats, order, sizes, first), *floats
            )
            return jax.tree_util.tree_map(jnp.add, grads, pull(g[slots // k]))

        grads = over_chunks(
            jax.tree_util.tree_map(jnp.zeros_like, floats), add, order, sizes
        )
        return (*grads, None, None)

    partial_result.defvjp(forward, backward)
    partial = partial_result(
        x, gate, up, down, weights.reshape(-1).astype(x.dtype), order, sizes
    )
    return partial, sizes


class TokenDecoder(nn.Module):
    """``(batch, L, F)`` scaled values → ``(batch, L, F)``: the expected bin
    centre of the row after each position, every tag a sequence. A kind
    declares these fields first, its own after them."""

    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: Tuple[int, ...]
    experts_per_token: int
    rms_norm_eps: float
    # recompute a layer's activations on the backward pass (the same trade
    # as the patchtst kind's): fleet builds read it as "memory-constrained"
    remat: bool

    # -- what a kind brings ---------------------------------------------------
    def _attention(self, p, x, **how):
        """One sequence ``(L, D)``, its norm first."""
        raise NotImplementedError

    def _route(self, p, tokens):
        """``(chosen (T, k), weights (T, k))`` over ALL experts."""
        raise NotImplementedError

    def _trunk(self, ids):
        """``ids (S, L)`` → the last layer's output, before its norm, and
        what the layers counted."""
        raise NotImplementedError

    # -- the parameter tree ---------------------------------------------------
    def _token_ends(self):
        """``embed``, ``head``, ``final_norm``: every kind's first three."""
        D, V = self.hidden_size, self.vocab_size
        self.embed = self.param("embed", _draw_matrix, (V, D))
        self.head = self.param("head", _draw_matrix, (D, V))
        self.final_norm = self.param("final_norm", nn.initializers.ones, (D,))

    def _expert_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, I, E = self.hidden_size, self.moe_intermediate_size, len(self.experts_held)
        return {
            "router": (D, self.n_routed_experts),
            "experts_gate": (E, D, I), "experts_up": (E, D, I),
            "experts_down": (E, I, D),
        }

    # -- layers ---------------------------------------------------------------
    def _recomputed(self, fn):
        """``fn``, its activations made again on the backward pass where the
        model recomputes (``remat``): what goes into ``fn`` is all that is
        kept of it."""
        return jax.checkpoint(fn) if self.remat else fn

    def _per_sequence(self, fn, x):
        """``fn (L, D) → (L, D)`` of each sequence of ``x (S, L, D)`` in turn:
        attention mixes within a sequence and every other product is a
        token's own, so one sequence's activations are the most a block of
        the layer holds, forward or (``remat``) backward."""
        return jax.lax.map(self._recomputed(fn), x)

    def _experts(self, p, x):
        """All the tokens ``(S, L, D)`` together (the slots are sorted over
        them), their norm first: ``(given, what the layer counts)``."""
        S, L, D = x.shape
        tokens = rms_norm(x, p["ffn_norm"], self.rms_norm_eps).reshape(S * L, D)
        if "shared_gate" in p:
            with jax.named_scope("shared_expert"):
                out = swiglu(tokens, p["shared_gate"], p["shared_up"], p["shared_down"])
        with jax.named_scope("expert_route"):
            chosen, weights = self._route(p, tokens)
        partial, sizes = grouped_experts(
            tokens, chosen, weights, self.experts_held, self.n_routed_experts,
            p["experts_gate"], p["experts_up"], p["experts_down"],
        )
        if "shared_gate" in p:
            partial = out + partial
        partial = self._normed_out(p, "post_ffn_norm", partial)
        return partial.reshape(S, L, D), self._counted(chosen, sizes)

    def _counted(self, chosen, sizes):
        """What an expert layer counts, from the experts its tokens chose
        ``(T, k)`` and the token-slots a held expert received ``(E,)``: those
        slots, unless a kind counts more."""
        return sizes

    def _normed_out(self, p, name, y):
        """A sub-block's output, normed by ``p[name]`` where the layer holds
        such a norm (``post_attn_norm``, ``post_ffn_norm``), else as it is."""
        return rms_norm(y, p[name], self.rms_norm_eps) if name in p else y

    def _layer(self, p, x, **how):
        """``x (S, L, D) → (x, what the expert layer counted)``; ``how`` goes
        to the kind's attention. Where the model recomputes, the layer's
        input and its attention's output are what the backward pass keeps of
        it."""
        x = x + self._per_sequence(
            lambda x_s: self._normed_out(
                p, "post_attn_norm", self._attention(p, x_s, **how)
            ), x,
        )
        if "w_gate" in p:
            return x + self._per_sequence(
                lambda x_s: self._normed_out(p, "post_ffn_norm", swiglu(
                    rms_norm(x_s, p["ffn_norm"], self.rms_norm_eps),
                    p["w_gate"], p["w_up"], p["w_down"],
                )), x,
            ), None
        given, counted = self._recomputed(self._experts)(p, x)
        return x + given, counted

    # -- the token front and back end -----------------------------------------
    def _over_vocabulary(self, norm, read, h, *rest):
        """``read(logits (L, V), ...)`` of each sequence in turn: one
        sequence's logits are the most that is held, made again in the
        backward pass where the model recomputes (``remat``)."""
        def one(head, norm, h_s, *rest_s):
            logits = rms_norm(h_s, norm, self.rms_norm_eps) @ head
            return read(logits.astype(jnp.float32), *rest_s)

        one = self._recomputed(one)
        return jax.lax.map(lambda args: one(self.head, norm, *args), (h,) + rest)

    def _bins(self, values):
        V = self.vocab_size
        return jnp.clip(jnp.floor(values * V), 0, V - 1).astype(jnp.int32)

    def _sequences(self, windows):
        B, L, F = windows.shape
        return jnp.swapaxes(self._bins(windows), 1, 2).reshape(B * F, L)

    # -- what the estimator and the train step call ---------------------------
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        B, L, F = x.shape
        h, _ = self._trunk(self._sequences(x))
        V = self.vocab_size
        centres = (jnp.arange(V, dtype=jnp.float32) + 0.5) / V
        expected = self._over_vocabulary(
            self.final_norm,
            lambda logits: jax.nn.softmax(logits, axis=-1) @ centres, h,
        )
        return jnp.swapaxes(expected.reshape(B, F, L), 1, 2)

    def _next_row(self, x, targets):
        """``(ids_next (S, L), h, what the trunk counted, the next row's
        cross-entropy (S, L))``."""
        ids, ids_next = self._sequences(x), self._sequences(targets)
        h, counts = self._trunk(ids)
        with jax.named_scope("token_loss"):
            nxt = self._over_vocabulary(
                self.final_norm, _cross_entropy, h, ids_next
            )
        return ids_next, h, counts, nxt


def per_sample(a, n_samples: int, n_tags: int):
    """``(B * F, L)`` per position → ``(B,)``: the mean over tags and
    positions."""
    return jnp.mean(a.reshape(n_samples, n_tags, a.shape[-1]), axis=(1, 2))


def _cross_entropy(logits, ids):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
