"""Plain reference for ``model_type: mixtral`` (Mixtral of Experts,
arXiv:2401.04088): the Mistral block with the MLP replaced by
``num_local_experts`` SwiGLU experts, of which a linear router picks
``num_experts_per_tok`` per token and mixes them by the renormalised softmax
weights. Every expert is computed for every token and masked by the combine
weights: plain, not fast. The blocks are in ``decoder.py``."""

from benchmarks.reference.decoder import logits, loss  # noqa: F401
