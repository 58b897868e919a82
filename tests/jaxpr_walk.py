"""Walking a traced program for the tests that count its kernels."""


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a
    Pallas kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def products(equations, lhs, rhs):
    """The ``dot_general`` equations among ``equations`` whose two operands
    have the shapes ``lhs`` and ``rhs``."""
    want = (tuple(lhs), tuple(rhs))
    return [e for e in equations if e.primitive.name == "dot_general"
            and tuple(tuple(v.aval.shape) for v in e.invars) == want]
