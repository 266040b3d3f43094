"""A second model family for the tests: the dense decoder under another
name, noting each function of the interface as it is called, so that a
test can see the harness reach all of them through ``spec.family``."""
from families import INTERFACE
from harness import spec

CALLED: set = set()
_dense = spec.family({"family": "dense"})


def _noting(name):
    fn = getattr(_dense, name)

    def call(*args, **kwargs):
        CALLED.add(name)
        return fn(*args, **kwargs)
    return call


for _name in INTERFACE:
    globals()[_name] = _noting(_name)
