"""Bytes the hyper-connections of the ``moe_lm`` family's step must move
between HBM and the chip, whatever implements them: the least a residual path
of ``n = hc_mult`` streams can do per sub-layer ``F``, from shapes alone.

``S`` is the streams of a chip's batch (``rows x seq_len x n x hidden`` in
the configuration's ``dtype``), ``H`` one stream's worth (``S / n``: ``h``,
``y`` and their cotangents). The maps, ``z`` and ``phi`` are kilobytes to a
megabyte and are not counted. Per sub-layer:

* before ``F`` — read ``X`` (S), write ``h`` (H): ``1/rms``, ``z`` and ``h``
  all come of one reading;
* after ``F`` — read ``X`` (S) and ``y`` (H), write ``X'`` (S);

forward ``3 S + 2 H``, and the same again where the traffic recomputes its
blocks (``remat``): the recomputed pass is work the step does, so it counts
here (unlike an operation of ``flops.py``, which a recomputation repeats but
a model does not need twice);

* the merge's backward — read ``dX'`` (S), ``X`` (S), ``y`` (H), write its
  ``dX`` (S) and ``dy`` (H);
* the maps' backward — read ``X`` (S), ``dh`` (H) and that ``dX`` (S), write
  the whole ``dX`` (S);

backward ``6 S + 3 H``. A layer has two sub-layers (attention, feed-forward).
"""

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def stream_bytes(cfg, rows, seq_len):
    """``(S, H)`` in bytes."""
    one = rows * seq_len * cfg["hidden_size"] * _ITEMSIZE[cfg["dtype"]]
    return cfg["hc_mult"] * one, one


def sublayer_bytes(cfg, rows, seq_len, remat):
    """``(forward, backward)`` bytes of one sub-layer's residual path, the
    forward counted twice where ``remat`` recomputes it."""
    streams, one = stream_bytes(cfg, rows, seq_len)
    return (2 if remat else 1) * (3 * streams + 2 * one), 6 * streams + 3 * one


def step_bytes(cfg, rows, seq_len, remat):
    """Bytes of a whole step's hyper-connections on one chip."""
    return 2 * cfg["num_hidden_layers"] * sum(sublayer_bytes(cfg, rows, seq_len, remat))
