"""``kernel_roofline`` over some kinds of a program's kernel calls only:
where one program holds several Pallas kernels under names of their own
(``pl.pallas_call(..., name=)``), each has its own share of its roofline.

The keys of ``kernel_roofline``, with ``match`` finding that kernel's
operations alone, and ``kinds``: the kinds of the glue's ``calls`` list
(``[(kind, flops, bytes)]``) that are this kernel's calls. With every
kind of the list and the same ``match`` it reads what ``kernel_roofline``
reads.
"""

import copy
import types

from . import kernel_roofline


def read(spec, run):
    fn = getattr(run.model, spec["calls"], None)
    if fn is None:
        return None
    kinds = set(spec["kinds"])

    def of_these_kinds(*args):
        return [c for c in fn(*args) if c[0] in kinds]

    view = copy.copy(run)
    view.model = types.SimpleNamespace(**{spec["calls"]: of_these_kinds})
    return kernel_roofline.read(spec, view)
