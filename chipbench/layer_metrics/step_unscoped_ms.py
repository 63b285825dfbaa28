"""Device milliseconds per training step in operations under no phase of
the step (compiler-inserted copy waits, the resident rows' copies), less
what a model scope names all the same: the compiler's ``ragged-dot``
calls carry no phase and are ``moe_experts_ms``'s, and a fusion left
without a name whose operations are all one scope's of
``step_parts.TABLE`` is that scope's metric's. Device trace."""

from chipbench import step_parts


def read(ctx):
    return step_parts.unscoped_ms(ctx)
