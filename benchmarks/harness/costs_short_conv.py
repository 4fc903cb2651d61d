"""Bytes that the gated short-convolution layers of a served model (LFM2)
need, from shapes, beside ``costs.py``: what the algorithm requires, never
what an implementation executes (no lane's padding, no tile's zero rows, no
pass made twice), so a share computed from them cannot pass 100% unless the
time leaves work out.

A configuration states the layers under ``layer_types`` (``"conv"``) and the
taps under ``conv_L_cache``: the convolution is depthwise over
``hidden_size`` channels and looks back ``conv_L_cache - 1`` rows, which is
ALL such a layer keeps of a sequence (no recurrent state). At the published
LFM2-24B-A2B: 2 rows x 2,048 x 2 bytes = 8,192 bytes a layer a sequence
whatever its length.

A decode step must read AND write each live row's tail once a layer, and
read the row's token in and write its convolved row out (``shift_bytes``:
2 x 8,192 + 2 x 4,096 = 24,576 bytes a row a layer); the program does all of
it under its ``conv/shift`` scope (``SHIFT_SCOPE``). The taps are 12 KB a
layer and left out. The mixer's two projections and its gates are plain
matmuls and products under ``conv/in_proj`` and ``conv/out_proj``
(``OWN_SCOPES``: all three).
"""

CONV = "conv"
SHIFT_SCOPE = "/conv/shift"
#: the program's scopes whose time is the short-convolution mixers'
OWN_SCOPES = ("/conv/in_proj", SHIFT_SCOPE, "/conv/out_proj")


def has_short_conv(hf: dict) -> bool:
    """Whether the configuration states short-convolution layers the way
    this module reads them."""
    return CONV in (hf.get("layer_types") or ()) and "conv_L_cache" in hf


def conv_layers(hf: dict) -> int:
    return list(hf["layer_types"]).count(CONV)


def layer_tail_bytes(hf: dict, itemsize: int) -> int:
    """One sequence's convolution tail in one layer: all the layer keeps."""
    return (int(hf["conv_L_cache"]) - 1) * int(hf["hidden_size"]) * itemsize


def sequence_tail_bytes(hf: dict, itemsize: int) -> int:
    """What a sequence holds in the conv layers, whatever its length."""
    return conv_layers(hf) * layer_tail_bytes(hf, itemsize)


def shift_bytes(rows: int, hf: dict, itemsize: int) -> int:
    """A decode step of ``rows`` live rows: each row's tail read and written
    once a layer, its token's row in and its convolved row out."""
    row = int(hf["hidden_size"]) * itemsize
    return int(rows) * conv_layers(hf) * 2 \
        * (layer_tail_bytes(hf, itemsize) + row)


def is_short_conv_op(op) -> bool:
    """An operation traced under one of the program's ``OWN_SCOPES``."""
    return any(scope in op.scope for scope in OWN_SCOPES)
