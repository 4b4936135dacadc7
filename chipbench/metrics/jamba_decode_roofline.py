"""The decode tick's share of its roofline on the chip, in percent: the
compulsory bytes of the window's ticks (the non-expert weights once a
tick, the experts the ticks hit, from ``moe.experts_hit``, the KV at
the actual lengths, each slot's recurrent state read and written;
``reference/jamba.py``'s ``decode_work``) at the HBM peak, over the
chip's busy time."""

from chipbench.metrics._roofline import share


def read(rec):
    return share(rec, "jamba_decode")
