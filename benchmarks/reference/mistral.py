"""Plain reference for ``model_type: mistral`` (Mistral 7B, arXiv:2310.06825):
pre-norm decoder, grouped-query attention with rotary embedding and a sliding
window, SwiGLU MLP, untied output head. The blocks are in ``decoder.py``."""

from benchmarks.reference.decoder import logits, loss  # noqa: F401
