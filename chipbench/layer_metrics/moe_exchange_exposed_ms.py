"""Milliseconds per step of ``moe_exchange_ms`` during which no other
operation runs on the chip: the exchange that the layer's other work does
not hide. Device trace."""

from chipbench import ep_scopes


def read(ctx):
    return ep_scopes.exposed_ms(ctx)
