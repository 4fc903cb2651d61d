"""Bytes and operations that the state-space (Mamba-2) layers of a served
model need, from shapes, beside ``costs.py``: what the algorithm requires,
never what an implementation executes (no lane's padding, no block's masked
pair, no pass made twice), so a share computed from them cannot pass 100%
unless the time leaves work out.

A configuration states the layers under ``layer_types`` (``"mamba"``) and a
layer's state under ``mamba_n_heads`` x ``mamba_d_head`` x ``mamba_d_state``,
float32 whatever the model is served in (a sum over every token a sequence
has seen); the convolution looks back ``mamba_d_conv - 1`` rows of
``mamba_expand * hidden_size + 2 * mamba_n_groups * mamba_d_state`` channels.
At the published granite-4.0-h-micro: 36 layers x (2,097,152 + 26,112) =
76,437,504 bytes a sequence whatever its length, beside 4 attention layers x
2 x 8 heads x 64 x 2 = 8,192 bytes of pages a token.

A decode step must read AND write each live row's state and its
convolution tail once a layer (``update_bytes``: ISSUE 44's definition; the
tail is 1.2% as much as the state, and the program moves it under its
``ssm/conv`` scope, so a decode step's time is what lies under ``ssm/conv``
and ``ssm/update`` together: ``DECODE_SCOPES``). A prefill chunk's recurrence is, a token a head, ``mamba_d_head *
mamba_d_state`` state elements each decayed (1 operation), stepped by ``x
(x) B`` (2) and read out by ``C`` (2): 5 (``scan_flops``); its bytes are the
rows in (``[x ; B ; C]`` and the step) and out (``y``) in the compute type
and each chunk's state read and written once a layer (``scan_bytes``).

``is_ssm_op`` selects the device operations the readers of these costs
(``metrics/ssm_*.py``) call the state-space layers' own.
"""

MAMBA, ATTENTION = "mamba", "attention"
#: the program's scopes whose time is the mixers' own (their two projections
#: are plain matmuls and are not counted)
OWN_SCOPES = ("/ssm/conv", "/ssm/scan", "/ssm/update", "/ssm/norm")
SCAN_SCOPE = "/ssm/scan"
#: in a decode step program: the tail's shift and the state's update
DECODE_SCOPES = ("/ssm/conv", "/ssm/update")
OPS_A_STATE_ELEMENT = 5


def has_state(hf: dict) -> bool:
    """Whether the configuration states state-space layers the way this
    module reads them."""
    return MAMBA in (hf.get("layer_types") or ()) and "mamba_d_state" in hf


def state_layers(hf: dict) -> int:
    return list(hf["layer_types"]).count(MAMBA)


def inner_width(hf: dict) -> int:
    return int(hf["mamba_n_heads"]) * int(hf["mamba_d_head"])


def conv_channels(hf: dict) -> int:
    """``[x ; B ; C]``: what the convolution runs over."""
    return inner_width(hf) + 2 * int(hf.get("mamba_n_groups", 1)) \
        * int(hf["mamba_d_state"])


def layer_state_bytes(hf: dict) -> int:
    """One sequence's recurrent state in one layer, float32."""
    return inner_width(hf) * int(hf["mamba_d_state"]) * 4


def layer_tail_bytes(hf: dict, itemsize: int) -> int:
    """One sequence's convolution tail in one layer."""
    return (int(hf["mamba_d_conv"]) - 1) * conv_channels(hf) * itemsize


def sequence_state_bytes(hf: dict, itemsize: int) -> int:
    """What a sequence holds in the state layers, whatever its length."""
    return state_layers(hf) * (layer_state_bytes(hf)
                               + layer_tail_bytes(hf, itemsize))


def page_token_bytes(hf: dict, itemsize: int) -> int:
    """K and V of one token over the attention layers."""
    heads = int(hf["num_attention_heads"])
    head_dim = hf.get("head_dim") or int(hf["hidden_size"]) // heads
    return list(hf["layer_types"]).count(ATTENTION) * 2 \
        * int(hf.get("num_key_value_heads", heads)) * head_dim * itemsize


def update_bytes(rows: int, hf: dict, itemsize: int) -> int:
    """A decode step of ``rows`` live rows: each row's state and
    convolution tail read and written once a layer."""
    return int(rows) * 2 * sequence_state_bytes(hf, itemsize)


def scan_flops(tokens: int, hf: dict) -> int:
    """The recurrence over ``tokens`` real tokens of prefill chunks."""
    return int(tokens) * state_layers(hf) * OPS_A_STATE_ELEMENT \
        * inner_width(hf) * int(hf["mamba_d_state"])


def scan_bytes(chunks: int, tokens: int, hf: dict, itemsize: int) -> int:
    """``chunks`` prefill chunks of ``tokens`` real tokens together: the
    rows in and out, and each chunk's state in and out."""
    rows = conv_channels(hf) + int(hf["mamba_n_heads"]) + inner_width(hf)
    return state_layers(hf) * (int(tokens) * rows * itemsize
                               + int(chunks) * 2 * layer_state_bytes(hf))


def scan_least_seconds(chunks: int, tokens: int, hf: dict, itemsize: int,
                       flops_per_s: float, bytes_per_s: float) -> float:
    """The larger of operations over the peak and bytes over the
    bandwidth."""
    return max(scan_flops(tokens, hf) / flops_per_s,
               scan_bytes(chunks, tokens, hf, itemsize) / bytes_per_s)


def is_ssm_op(op) -> bool:
    """An operation traced under one of the program's ``OWN_SCOPES``."""
    return any(scope in op.scope for scope in OWN_SCOPES)
