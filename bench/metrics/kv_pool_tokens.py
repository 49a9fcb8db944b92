"""Tokens of KV the pool holds, as allotted at set-up (pages, less the
trash page, times the page size)."""


def read(run, trace):
    return run["counters"]["kv_pool_tokens"]
