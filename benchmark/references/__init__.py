"""Plain references that configurations bring of their own, one file a
configuration: <config name>.py with `shards(dat, code, large, small,
which)`, found by spec.reference. Empty until a configuration's code
is one that reference.py does not describe."""
