"""owner_bits_us_per_row.hr: wire.owner_bits stage seconds (the native
owner-bitplane pack, a part of wire.encode) over the rows driven, in
microseconds (native/__init__.py owner_bits_native); None where the
program has no such stage."""


def read(r):
    return r.per_row(r.stage_seconds("wire.owner_bits"), 1e6)
