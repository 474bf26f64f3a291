"""A test architecture module: the dense one, with every FLOP and byte count
doubled.  The readers that count work have to read twice as much."""
from chipbench import reference as dense
from chipbench.reference import (  # noqa: F401
    Reference, dims_of, make_params, program_config)


def reprefill_flops(*args, **kw):
    return 2 * dense.reprefill_flops(*args, **kw)


def decode_token_flops(*args, **kw):
    return 2 * dense.decode_token_flops(*args, **kw)


def decode_attention_cost(*args, **kw):
    flops, byts = dense.decode_attention_cost(*args, **kw)
    return 2 * flops, 2 * byts
