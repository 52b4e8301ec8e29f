"""Plain reference of the host-side protocol arithmetic: task seed, IPFS
CIDv0 of a solution (UnixFS file in 256 KiB chunks, wrapped in a
directory), and the commitment keccak256(abi.encode(address, bytes32
taskid, bytes cid)). Written from the specifications (dag-pb / UnixFS,
Solidity ABI, Keccak-f[1600]); imports nothing of the program.
"""
from __future__ import annotations

import hashlib

CHUNK = 262144
MAX_LINKS = 174
SEED_MODULUS = 0x1FFFFFFFFFFFF0  # Number.MAX_SAFE_INTEGER - 15


def task_seed(taskid: bytes) -> int:
    return int.from_bytes(taskid, "big") % SEED_MODULUS


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(tag: int, payload: bytes) -> bytes:
    return bytes([tag]) + _varint(len(payload)) + payload


def _cid(block: bytes) -> bytes:
    return b"\x12\x20" + hashlib.sha256(block).digest()


def _link(cid: bytes, name: str, tsize: int) -> bytes:
    return _field(0x12, _field(0x0A, cid) + _field(0x12, name.encode())
                  + b"\x18" + _varint(tsize))


def _file_dag(content: bytes) -> tuple[bytes, int]:
    """(cid, cumulative size) of a UnixFS file, balanced, width 174."""
    level = []
    for i in range(0, max(len(content), 1), CHUNK):
        ch = content[i:i + CHUNK]
        unixfs = b"\x08\x02" + (_field(0x12, ch) if ch else b"") \
            + b"\x18" + _varint(len(ch))
        block = _field(0x0A, unixfs)
        level.append((_cid(block), len(block), len(ch)))
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level), MAX_LINKS):
            kids = level[i:i + MAX_LINKS]
            size = sum(k[2] for k in kids)
            unixfs = b"\x08\x02\x18" + _varint(size) \
                + b"".join(b"\x20" + _varint(k[2]) for k in kids)
            block = b"".join(_link(k[0], "", k[1]) for k in kids) \
                + _field(0x0A, unixfs)
            nxt.append((_cid(block), len(block) + sum(k[1] for k in kids),
                        size))
        level = nxt
    return level[0][0], level[0][1]


def solution_cid(files: dict[str, bytes]) -> bytes:
    """CID of the directory that wraps the solution's files (34 bytes)."""
    links = b""
    for name in sorted(files):
        cid, tsize = _file_dag(files[name])
        links += _link(cid, name, tsize)
    return _cid(links + _field(0x0A, b"\x08\x01"))


_RC = [0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
       0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
       0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
       0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
       0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
       0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
       0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
       0x8000000000008080, 0x0000000080000001, 0x8000000080008008]
_ROT = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
        [28, 55, 25, 21, 56], [27, 20, 39, 8, 14]]
_M = (1 << 64) - 1


def _rol(x: int, n: int) -> int:
    return ((x << n) | (x >> (64 - n))) & _M if n else x


def _keccak_f(a):
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        a = [[a[x][y] ^ d[x] for y in range(5)] for x in range(5)]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        a = [[b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
              for y in range(5)] for x in range(5)]
        a[0][0] ^= rc
    return a


def keccak256(data: bytes) -> bytes:
    """Original Keccak padding (0x01), rate 136 — Ethereum's hash."""
    rate = 136
    msg = bytearray(data) + b"\x01" + b"\x00" * (-(len(data) + 1) % rate)
    msg[-1] |= 0x80
    a = [[0] * 5 for _ in range(5)]
    for off in range(0, len(msg), rate):
        for i in range(rate // 8):
            a[i % 5][i // 5] ^= int.from_bytes(
                msg[off + 8 * i:off + 8 * i + 8], "little")
        a = _keccak_f(a)
    return b"".join(a[i % 5][i // 5].to_bytes(8, "little")
                    for i in range(4))


def commitment(address: str, taskid: bytes, cid: bytes) -> bytes:
    """keccak256(abi.encode(address, bytes32, bytes))."""
    addr = bytes.fromhex(address[2:]).rjust(32, b"\x00")
    tail = len(cid).to_bytes(32, "big") + cid.ljust(-(-len(cid) // 32) * 32,
                                                     b"\x00")
    return keccak256(addr + taskid.rjust(32, b"\x00")
                     + (96).to_bytes(32, "big") + tail)
