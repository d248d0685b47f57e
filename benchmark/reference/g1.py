"""BLS12-381's G1 in plain Python, for ``eth_aggregate_pubkeys``
(consensus-specs specs/altair/bls.md) and nothing else: decompression of a
48-byte key by its three flag bits, ``KeyValidate`` (not the identity, in
the subgroup by [r]P = O), addition in Jacobian coordinates, compression.
The curve is y^2 = x^3 + 4 over the base field; the constants are the
curve's published ones. It imports nothing of the program.

Every key this module meets is a registry key in compressed form; it
refuses anything else (an encoding without the compression bit, a key that
fails ``KeyValidate``) with ``ValueError``."""

from __future__ import annotations

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
B = 4
GENERATOR = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)

COMPRESSION_FLAG, INFINITY_FLAG, SORT_FLAG = 0x80, 0x40, 0x20
KEY_BYTES = 48
IDENTITY = (1, 1, 0)  # Jacobian: Z = 0


def to_jacobian(point) -> tuple:
    """An affine (x, y), or None for the identity."""
    return IDENTITY if point is None else (point[0], point[1], 1)


def to_affine(point: tuple):
    x, y, z = point
    if z == 0:
        return None
    z_inv = pow(z, P - 2, P)
    z_inv2 = z_inv * z_inv % P
    return x * z_inv2 % P, y * z_inv2 * z_inv % P


def double(point: tuple) -> tuple:
    """dbl-2009-l (a = 0)."""
    x, y, z = point
    if z == 0 or y == 0:
        return IDENTITY
    a = x * x % P
    b = y * y % P
    c = b * b % P
    d = 2 * ((x + b) * (x + b) - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    y3 = (e * (d - x3) - 8 * c) % P
    z3 = 2 * y * z % P
    return x3, y3, z3


def add(p1: tuple, p2: tuple) -> tuple:
    """add-2007-bl, with the doubling and the identity cases."""
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    if z1 == 0:
        return p2
    if z2 == 0:
        return p1
    z1z1 = z1 * z1 % P
    z2z2 = z2 * z2 % P
    u1 = x1 * z2z2 % P
    u2 = x2 * z1z1 % P
    s1 = y1 * z2 * z2z2 % P
    s2 = y2 * z1 * z1z1 % P
    h = (u2 - u1) % P
    r = 2 * (s2 - s1) % P
    if h == 0:
        return double(p1) if r == 0 else IDENTITY
    i = 4 * h * h % P
    j = h * i % P
    v = u1 * i % P
    x3 = (r * r - j - 2 * v) % P
    y3 = (r * (v - x3) - 2 * s1 * j) % P
    z3 = ((z1 + z2) * (z1 + z2) - z1z1 - z2z2) * h % P
    return x3, y3, z3


def multiply(point: tuple, scalar: int) -> tuple:
    """[scalar]point, double and add from the top bit."""
    out = IDENTITY
    for bit in bin(scalar)[2:]:
        out = double(out)
        if bit == "1":
            out = add(out, point)
    return out


def decompress(data: bytes):
    """The affine point of a 48-byte compressed encoding, or None for the
    identity."""
    data = bytes(data)
    if len(data) != KEY_BYTES or not data[0] & COMPRESSION_FLAG:
        raise ValueError("not a compressed G1 point")
    sort = bool(data[0] & SORT_FLAG)
    x = int.from_bytes(data, "big") & ((1 << 381) - 1)
    if data[0] & INFINITY_FLAG:
        if sort or x:
            raise ValueError("a malformed identity")
        return None
    if x >= P:
        raise ValueError("x is not a field element")
    y_squared = (pow(x, 3, P) + B) % P
    y = pow(y_squared, (P + 1) // 4, P)  # P = 3 mod 4
    if y * y % P != y_squared:
        raise ValueError("not on the curve")
    if (y > (P - 1) // 2) != sort:
        y = P - y
    return x, y


def compress(point) -> bytes:
    """The 48-byte encoding of an affine point, or of the identity (None)."""
    if point is None:
        return bytes([COMPRESSION_FLAG | INFINITY_FLAG]) + b"\x00" * (KEY_BYTES - 1)
    x, y = point
    out = bytearray(x.to_bytes(KEY_BYTES, "big"))
    out[0] |= COMPRESSION_FLAG | (SORT_FLAG if y > (P - 1) // 2 else 0)
    return bytes(out)


def key_validate(data: bytes) -> tuple:
    """``KeyValidate``: the key's Jacobian point; raises unless it decodes,
    is not the identity and lies in the subgroup of order r."""
    point = decompress(data)
    if point is None:
        raise ValueError("a key cannot be the identity")
    jacobian = to_jacobian(point)
    if multiply(jacobian, R)[2] != 0:
        raise ValueError("not in the subgroup")
    return jacobian


def eth_aggregate_pubkeys(pubkeys: list) -> bytes:
    """specs/altair/bls.md: every key validated, then their sum, compressed.
    A key that repeats is validated once."""
    if not pubkeys:
        raise ValueError("no keys to aggregate")
    points: dict = {}
    total = IDENTITY
    for key in pubkeys:
        key = bytes(key)
        if key not in points:
            points[key] = key_validate(key)
        total = add(total, points[key])
    return compress(to_affine(total))
