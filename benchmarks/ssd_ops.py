"""Operations and bytes the Mamba-2 scans of the ``ssd_lm`` family's step need
in their chunked matrix form (``ops/ssd_scan.py``: kernels ``ssd_scan_fwd`` /
``ssd_scan_bwd``), from shapes alone, whatever implements the chunks.

**Operations.** A chunk of ``Q`` positions and a head of ``P`` channels and
``N`` states is three products: the chunk's own map ``((C B^T) * L) xd`` (``2 Q
Q P``), the carried state read by ``C`` (``2 Q N P``) and the chunk's state
``xd^T (w * B)`` (``2 Q P N``); ``C B^T`` (``2 Q Q N``) is shared by a group's
heads and counted once a group. The backward pass is twice the forward
(``flops.py``'s convention); what an implementation computes again in it is not
counted.

**Bytes.** Forward a position: ``x`` (``H P``), ``B`` and ``C`` (``G N``
each) in the configuration's ``dtype`` and the step ``Delta`` (``H`` float32)
in, ``y`` (``H P``) out. Backward: those four and ``dy`` in; the cotangents of
``x``, ``Delta``, ``B`` and ``C`` out. The chunks' states ``[H, L / Q, P, N]``
float32 once each way (written forward, read backward). ``a``, the skip and
their gradients are a few hundred bytes and are not counted, nor is anything
an implementation keeps for itself.
"""

from benchmarks import flops_ssd_lm

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(cfg):
    """``(H, G, P, N, Q)`` of the heads and groups held here."""
    return (cfg["mamba_num_heads"], cfg["n_groups"], cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["chunk_size"])


def layer_flops(cfg, rows, seq_len):
    """Forward + backward operations of one Mamba-2 block's scan."""
    heads, groups, width, states, chunk = sizes(cfg)
    chunks = rows * -(-seq_len // chunk)
    forward = chunks * (heads * (2 * chunk * chunk * width + 4 * chunk * states * width) + groups * 2 * chunk * chunk * states)
    return 3 * forward


def layer_bytes(cfg, rows, seq_len):
    """Forward + backward bytes of one Mamba-2 block's scan."""
    heads, groups, width, states, chunk = sizes(cfg)
    item = _ITEMSIZE[cfg["dtype"]]
    wide, step, both = heads * width * item, heads * 4, 2 * groups * states * item
    forward = 2 * wide + step + both
    backward = (2 * wide + step + both) + (wide + step + both)
    carried = 2 * rows * heads * -(-seq_len // chunk) * width * states * 4
    return rows * seq_len * (forward + backward) + carried


def step_flops(cfg, rows, seq_len):
    return flops_ssd_lm.block_kinds(cfg).count("M") * layer_flops(cfg, rows, seq_len)


def step_bytes(cfg, rows, seq_len):
    return flops_ssd_lm.block_kinds(cfg).count("M") * layer_bytes(cfg, rows, seq_len)
