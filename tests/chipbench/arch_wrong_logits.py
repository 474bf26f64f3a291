"""A test architecture module: the dense one, with a reference whose logits
are a quarter too large.  A run whose configuration names it has to come out
not correct."""
from chipbench.reference import (  # noqa: F401
    decode_attention_cost, decode_token_flops, dims_of, make_params,
    program_config, reprefill_flops)
from chipbench.reference import Reference as Dense


class Reference(Dense):
    def request(self, *args, **kw):
        logits, scores, selections = super().request(*args, **kw)
        return 1.25 * logits, scores, selections
