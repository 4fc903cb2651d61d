"""Routed experts through a grouped matmul: only the chosen experts compute.

Reference analog: ``deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm`` with
``ragged_ops/moe_scatter`` and ``moe_gather`` (rows sorted by expert, one
grouped GEMM a weight, rows gathered back). Here the T x K assignments are
sorted by expert and multiplied by a grouped matmul (a group's tiles are
visited for the rows it has), so no ``[E, T, F]`` intermediate exists and an
expert nobody chose is not read. ``models/joyai_llm_flash.py`` (the weights
that train: ``jax.lax.ragged_dot``, which differentiates) and
``inference/v2/modules.py`` (the weights that serve: the JoyAI-LLM-Flash,
Mixtral and Qwen2-MoE policies, each behind its own router; on a TPU through
``ops/pallas/grouped_matmul.py``) call this.
"""

import jax
import jax.numpy as jnp


def sigmoid_route(h, gate_kernel, bias, top_k: int, scaling: float):
    """(weights [T, K] float32, expert ids [T, K]) for ``h`` [T, D]:
    ``s = sigmoid(W_g h)`` in float32 at full matmul precision (the choice
    must not hang on a bfloat16 pass), the ``top_k`` experts with the
    largest ``s + bias`` (``noaux_tc``'s ``e_score_correction_bias``; it
    steers the choice and does not enter the weights), weights ``s`` of the
    chosen renormalised to sum to 1, times ``scaling``. Ties go to the lower
    id (``jax.lax.top_k`` is stable)."""
    logits = jnp.dot(h.astype(jnp.float32), gate_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, ids


def softmax_route(h, gate_kernel, top_k: int, norm_topk_prob: bool):
    """(weights [T, K] float32, expert ids [T, K]) for ``h`` [T, D]: softmax
    in float32 over all experts' logits, the ``top_k`` largest (ties to the
    lower id: ``jax.lax.top_k`` is stable), with ``norm_topk_prob`` the kept
    probabilities renormalised to sum to 1 (GShard/Mixtral; HF Qwen2-MoE
    runs with it off)."""
    logits = h.astype(jnp.float32) @ gate_kernel
    w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    return w, ids


def relu2(x):
    """``relu(x) ** 2``: an ungated expert's activation (``mlp_hidden_act:
    relu2``)."""
    return jnp.square(jnp.maximum(x, 0))


def grouped_expert_ffn(h, experts, weights, ids, valid=None,
                       matmul=jax.lax.ragged_dot, gate_up=None,
                       first: int = 0):
    """``sum_k weights[t, k] * E_ids[t, k](h[t])`` with each ``E`` a gated
    MLP of the stacked weights ``experts`` (``w_gate``, ``w_up`` [E, D, F];
    ``w_down`` [E, F, D]), or an UNGATED one of two matrices, ``W_down
    relu(W_in h) ** 2``, where ``experts`` is ``w_in`` and ``w_down``, BOTH
    [E, F, D]: a hidden unit's row in and its row out. (``F`` need be no
    multiple of the TPU's 128 lanes, Nemotron-H's 1,856 is not, and a device
    array whose last axis is not gets a layout with another axis minor, out
    of which a kernel's operand is copied whole, 640 MB a layer a step; ``D``
    last, the stack lies as the kernel reads it.) ``h``: [T, D] in the
    compute type; ``weights``, ``ids``: [T, K]; ``valid``: [T] bool, rows to
    leave out (bucket padding). ``matmul(rows, stacked weights, counts)`` is
    the grouped matmul, with ``jax.lax.ragged_dot``'s meaning; ``gate_up``,
    where given, computes the first product and its activation a group in one
    pass: ``gate_up(rows, w_gate, w_up, counts)`` = ``silu(rows @ w_gate) *
    (rows @ w_up)``, or of an ungated expert ``gate_up(rows, w_in, counts)``
    = ``relu2(rows @ w_in.T)``. Returns (y [T, D], rows on each expert [E]
    int32).

    ``experts`` may be a share of the router's: the ``E`` stacked here are the
    router's experts ``first .. first + E - 1`` (expert parallelism's share
    of one chip; the router keeps its full width and its top-k). An
    assignment to an expert that is not held is left out like a padding
    row's, its part of the sum is the absent chip's to add, and ``counts``
    are of the held experts alone.

    Every shape is static: the T*K assignments are sorted by expert with
    those left out last, the grouped matmul visits only the rows its group
    sizes cover, and the rows past them (uninitialised in its output) are
    zeroed before they are gathered back. The four kinds of work sit under
    a ``jax.named_scope`` each (``telemetry/names.py`` ``SERVED_LEAF_SCOPES``:
    ``moe/experts/sort``, ``gather``, ``matmul``, ``combine``), so that a
    device trace says what the wrapper costs beside the matmuls."""
    t, k = ids.shape
    e = experts["w_down"].shape[0]
    dtype = h.dtype
    with jax.named_scope("moe/experts/sort"):
        held = ids - first
        keep = (held >= 0) & (held < e)
        if valid is not None:
            keep = keep & valid[:, None]
        key = jnp.where(keep, held, e).reshape(-1)               # [T*K]
        order = jnp.argsort(key, stable=True)
        counts = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    with jax.named_scope("moe/experts/gather"):
        xs = h[order // k]                                       # [T*K, D]
    with jax.named_scope("moe/experts/matmul"):
        first_product = tuple(experts[name].astype(dtype)
                              for name in ("w_gate", "w_up", "w_in")
                              if name in experts)
        if gate_up is not None:
            act = gate_up(xs, *first_product, counts)
        elif "w_in" in experts:
            act = relu2(matmul(xs, jnp.swapaxes(first_product[0], 1, 2),
                               counts))
        else:
            act = jax.nn.silu(matmul(xs, first_product[0], counts)) * \
                matmul(xs, first_product[1], counts)
        out = matmul(act, experts["w_down"].astype(dtype), counts)
    with jax.named_scope("moe/experts/combine"):
        computed = jnp.arange(t * k) < jnp.sum(counts)
        out = jnp.where(computed[:, None], out, 0)
        back = out[jnp.argsort(order)].reshape(t, k, -1)
        w = jnp.where(keep, weights, 0.0).astype(dtype)
        return jnp.einsum("tk,tkd->td", w, back), counts
