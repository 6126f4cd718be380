"""Crossbar read (``kernels/crossbar_mac``): the least time the traced
steps' crossbar reads could take over the kernel's summed device time,
in %.

Work is counted from useful tokens and published shapes: every linear
of every layer per useful position, the head per emitted token.  One
read of a K x N weight for one row is ``in_bits * n_slices`` pulses x
slices, each a pos and a neg dot: ``2 * K * N * in_bits * n_slices * 2``
operations, which the chip could run at its int8 peak (operands are 0/1
pulses and cell codes below ``2**bits_per_cell``).  Bytes are every
crossbar weight's cell codes at their bit width
(``2 * n_slices * bits_per_cell`` bits per weight), read once per step,
plus one f32 scale per column, 8-bit inputs and f32 outputs.
"""
from work import linears

KERNEL = r"^%?crossbar_mac\."   # op name the v5e trace prints


def step_ops_bytes(conf, positions: int, tokens: int):
    xb = conf["crossbar"]
    n_sl = -(-xb["w_bits"] // xb["bits_per_cell"])
    per = 2 * xb["in_bits"] * n_sl * 2
    layer, (hk, hn) = linears(conf)
    n_layers = conf["num_hidden_layers"]
    trunk = n_layers * sum(k * n for _, k, n in layer)
    ops = per * (trunk * positions + hk * hn * tokens)
    bits = 2 * n_sl * xb["bits_per_cell"]
    weights = trunk + hk * hn
    cols = n_layers * sum(n for _, _, n in layer) + hn
    rows_in = n_layers * sum(k for _, k, _ in layer)
    io = positions * (rows_in + 4 * (cols - hn)) + tokens * (hk + 4 * hn)
    return ops, weights * bits / 8 + 4 * cols + io


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    kernel_s = run.trace.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    least = 0.0
    for s in run.traced_steps:
        ops, nbytes = step_ops_bytes(run.conf, s.positions, s.tokens)
        least += max(ops / run.peaks["int8_ops"],
                     nbytes / run.peaks["hbm_bytes_s"])
    return 100.0 * least / kernel_s
