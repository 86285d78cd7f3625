"""Requests per device call of the serving engine, from its exact
`requests` and `batches` counters over the traced span."""


def read(r):
    calls = r.counters.get("batches", 0)
    return r.counters["requests"] / calls if calls else None
