"""A step reference under another name, as a new architecture would
bring one: the plain decoder's functions, each call recorded."""
from reference import decoder

CALLS = []


def make_weights(*a, **kw):
    CALLS.append("make_weights")
    return decoder.make_weights(*a, **kw)


def token_pool(*a, **kw):
    CALLS.append("token_pool")
    return decoder.token_pool(*a, **kw)


def run(*a, **kw):
    CALLS.append("run")
    return decoder.run(*a, **kw)
