"""Device milliseconds per training step of the expert exchange's
collectives over ``ep``: the all-gather and reduce-scatter instructions
(known by HLO opcode, in any asynchronous form) under the scope
``moe_exchange``, forward and backward, the union a chip of their own
time and their asynchronous spans, averaged over the chips. Device
trace."""

from chipbench import ep_scopes


def read(ctx):
    return ep_scopes.exchange_ms(ctx)
