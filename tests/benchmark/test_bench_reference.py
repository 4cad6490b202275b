"""The plain reference: G1 arithmetic on Python integers and the
credential relations it judges with, against the native core."""

import random

from benchmark import reference as ref
from coconut_tpu.backend import get_backend

G = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)


def test_scalar_multiplication_matches_the_native_core():
    rng = random.Random(3)
    ks = [rng.randrange(ref.R) for _ in range(4)] + [1, 2, ref.R - 1]
    native = get_backend("cpp").msm_g1_shared([G], [[k] for k in ks])
    assert [ref.mul(G, k) for k in ks] == native
    assert ref.mul(G, ref.R) is None
    assert ref.in_subgroup(G) and ref.on_curve(G)
    assert not ref.on_curve((G[0], G[1] + 1))


def test_credential_relation():
    rng = random.Random(4)
    x, ys = rng.randrange(ref.R), [rng.randrange(ref.R) for _ in range(3)]
    msgs = [rng.randrange(ref.R) for _ in range(3)]
    h = ref.mul(G, rng.randrange(1, ref.R))
    s2 = ref.mul(h, ref.exponent(x, ys, msgs))
    assert ref.credential_valid(h, s2, x, ys, msgs)
    assert not ref.credential_valid(h, ref.mul(s2, 2), x, ys, msgs)
    assert not ref.credential_valid(h, s2, x, ys, [msgs[0] + 1] + msgs[1:])
    assert not ref.credential_valid(None, None, x, ys, msgs)
    t = rng.randrange(ref.R)
    s2t = ref.mul(h, ref.exponent(x, ys, msgs, t))
    assert ref.show_valid(h, s2t, x, ys, msgs, t)
    assert not ref.show_valid(h, s2t, x, ys, msgs, t + 1)


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(ref))
    mods = [
        n.module if isinstance(n, ast.ImportFrom) else a.name
        for n in ast.walk(tree)
        if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in (n.names if isinstance(n, ast.Import) else [n])
    ]
    assert not [m for m in mods if m and "coconut" in m]
