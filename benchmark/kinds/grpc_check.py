"""gRPC ``CheckService.Check``: one check a request.  The request is
encoded here, field by field, so that the load generator needs nothing of
the program: CheckRequest{tuple = 8: RelationTuple{namespace = 1,
object = 2, relation = 3, subject = 4: Subject{id = 1 | set = 2}}},
CheckResponse{allowed = 1}."""

from __future__ import annotations

import grpc

import checkmix
from graphs import drive

METHOD = "/ory.keto.relation_tuples.v1alpha2.CheckService/Check"
#: one channel a process: its threads multiplex one connection
SHARED_CLIENT = True


def _field(number: int, payload: bytes) -> bytes:
    n, size = len(payload), b""
    while n > 0x7F:
        size += bytes([n & 0x7F | 0x80])
        n >>= 7
    return bytes([number << 3 | 2]) + size + bytes([n]) + payload


def encode(namespace: str, obj: str, relation: str, subject) -> bytes:
    if isinstance(subject, tuple):
        sub = _field(2, b"".join(
            _field(i + 1, s.encode()) for i, s in enumerate(subject)))
    else:
        sub = _field(1, subject.encode())
    return _field(8, _field(1, namespace.encode()) + _field(2, obj.encode())
                  + _field(3, relation.encode()) + _field(4, sub))


def allowed(answer: bytes) -> bool:
    """CheckResponse.allowed: field 1, a varint, absent when false."""
    return answer[:2] == b"\x08\x01"


def make_pool(world, mix: dict, rng, n: int) -> list:
    r = checkmix.rows(world, mix, rng, n)
    base = world.G + world.F
    pool = []
    for i in range(n):
        g = int(r["group"][i])
        subject = (("Group", f"g{g}", "members") if g >= 0
                   else f"u{r['user'][i]}")
        wire = encode("Doc", f"d{r['obj'][i] - base}",
                      drive.RELATIONS[r["rel"][i]], subject)
        pool.append((wire, {k: v[i:i + 1] for k, v in r.items()}))
    return pool


def units(query) -> int:
    return 1


class Client:
    """One channel; the threads of a client process share it."""

    def __init__(self, address, timeout: float):
        self.timeout = timeout
        self.channel = grpc.insecure_channel("%s:%d" % tuple(address))
        self.check = self.channel.unary_unary(METHOD)

    def call(self, wire: bytes):
        try:
            return True, self.check(wire, timeout=self.timeout)
        except grpc.RpcError:
            return False, b""

    def close(self):
        self.channel.close()


def decode(query, answer: bytes):
    return [allowed(answer)]


def expected(ref, world, query) -> list:
    return checkmix.reference_verdicts(ref, query)
