"""The host-side reference for BGP answers.

:func:`bgp_oracle` evaluates a :class:`~repro.query.Query` by naive
pattern matching over ``kg.to_codes()`` in NumPy: nested loops over the
KG rows, one pattern at a time. It shares no code with the compiled query
path, which is what makes it the reference ``KGEngine.query`` is checked
against (the query tests and ``chip_smoke.py``). Cost grows with the KG
size to the power of the pattern count: use it on small KGs.
"""
from __future__ import annotations

import numpy as np


def bgp_oracle(kg, q) -> np.ndarray:
    """Naive BGP evaluation by pattern-matching over ``kg.to_codes()`` —
    the independent reference ``KGEngine.query`` must agree with. Returns
    the sorted distinct answer rows as an ``[n, k]`` int array (k = the
    width of ``q.answer_attrs()``)."""
    rows = np.asarray(kg.to_codes())
    kinds = q.var_kinds()

    def match(binding, pat, row):
        b = dict(binding)
        for pos, term, cols in (("s", pat.s, (0, 1)), ("p", pat.p, (2,)),
                                ("o", pat.o, (3, 4))):
            val = tuple(int(row[c]) for c in cols)
            if isinstance(term, str):
                name = term[1:]
                if name in b:
                    if b[name] != val:
                        return None
                else:
                    b[name] = val
            else:
                const = (term,) if pos == "p" else tuple(term)
                if const != val:
                    return None
        return b

    binds = [{}]
    for pat in q.patterns:
        binds = [m for b in binds for row in rows
                 for m in (match(b, pat, row),) if m is not None]
    for f in q.filters:
        name = f.var[1:]
        const = ((f.term,) if isinstance(f.term, int) else tuple(f.term))
        binds = [b for b in binds if (b[name] == const) == (f.op == "eq")]
    if not kinds:   # all-constant existence: the matching triple rows
        out = sorted(set(
            tuple(int(c) for c in row) for row in rows
            if match({}, q.patterns[0], row) is not None))
        return np.array(out, dtype=np.int32).reshape(len(out), 5)
    names = q.answer_vars()
    out = sorted(set(tuple(c for n in names for c in b[n]) for b in binds))
    width = sum(1 if kinds[n] == "pred" else 2 for n in names)
    return np.array(out, dtype=np.int32).reshape(len(out), width)
