"""Share of the bytes sent to the device that were padding: 1 less the
staging ledger's `payload_bytes` (volume bytes, as the encoder read
them) over its `h2d_bytes` (what was sent)."""


def read(ctx):
    s = ctx["staging"]
    if "payload_bytes" not in s or s.get("h2d_bytes", 0) <= 0:
        return None
    return 1.0 - s["payload_bytes"] / s["h2d_bytes"]
