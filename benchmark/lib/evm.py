"""The plain reference: a concrete EVM that replays a reported witness.

An analyzer's issue names a hazard (an SWC id at a program counter of the
runtime code) and a witness: the concrete transaction sequence that
reaches it. This module runs that sequence on a straightforward concrete
interpreter, written from the Yellow Paper semantics and importing
nothing of the system under test, and records what the hazard classes
the benchmark's contracts carry need to be confirmed:

* SWC-101: the ADD, MUL or SUB at the pc wrapped modulo 2**256 in a
  transaction that did not revert (a wrap whose transaction reverts
  leaves no state behind);
* SWC-106: the SELFDESTRUCT at the pc ran in a transaction that did not
  revert and whose sender is not the account that created the contract;
* SWC-115: the JUMPI at the pc branched on a value derived from ORIGIN.

Gas is not metered (no witness of these classes depends on it); a step
limit stops a runaway loop instead. An opcode outside the table raises
``ReplayError``: the reference cannot judge that witness, and a witness
it cannot judge is not confirmed.
"""

from typing import Dict, List, Optional, Tuple

from benchmark.lib.keccak import keccak256

WORD = 1 << 256
MASK = WORD - 1
STEP_LIMIT = 1_000_000


class ReplayError(Exception):
    """The witness cannot be replayed by the reference."""


class _Revert(Exception):
    pass


def _signed(v: int) -> int:
    return v - WORD if v >> 255 else v


class Frame:
    """One execution of code, with its stack (values and ORIGIN taint)."""

    def __init__(self, code: bytes, calldata: bytes, caller: int, origin: int,
                 value: int, address: int, storage: Dict[int, int]):
        self.code = code
        self.calldata = calldata
        self.caller = caller
        self.origin = origin
        self.value = value
        self.address = address
        self.storage = storage
        self.stack: List[Tuple[int, bool]] = []
        self.memory = bytearray()
        self.jumpdests = self._jumpdests(code)

    @staticmethod
    def _jumpdests(code: bytes) -> set:
        dests, pc = set(), 0
        while pc < len(code):
            op = code[pc]
            if op == 0x5B:
                dests.add(pc)
            pc += 1 + (op - 0x5F if 0x60 <= op <= 0x7F else 0)
        return dests

    def pop(self) -> Tuple[int, bool]:
        if not self.stack:
            raise _Revert("stack underflow")
        return self.stack.pop()

    def push(self, value: int, taint: bool = False) -> None:
        if len(self.stack) >= 1024:
            raise _Revert("stack overflow")
        self.stack.append((value & MASK, taint))

    def mem(self, offset: int, size: int) -> bytearray:
        if size == 0:
            return bytearray()
        if offset + size > 1 << 24:
            raise _Revert("memory out of range")
        if len(self.memory) < offset + size:
            self.memory.extend(b"\x00" * (offset + size - len(self.memory)))
        return self.memory[offset : offset + size]

    def mem_write(self, offset: int, data: bytes) -> None:
        self.mem(offset, len(data))
        self.memory[offset : offset + len(data)] = data


_BINARY = {
    0x01: lambda a, b: a + b,
    0x02: lambda a, b: a * b,
    0x03: lambda a, b: a - b,
    0x04: lambda a, b: a // b if b else 0,
    0x05: lambda a, b: (abs(_signed(a)) // abs(_signed(b)))
    * (-1 if (_signed(a) < 0) != (_signed(b) < 0) else 1) if b else 0,
    0x06: lambda a, b: a % b if b else 0,
    0x0A: lambda a, b: pow(a, b, WORD),
    0x10: lambda a, b: int(a < b),
    0x11: lambda a, b: int(a > b),
    0x12: lambda a, b: int(_signed(a) < _signed(b)),
    0x13: lambda a, b: int(_signed(a) > _signed(b)),
    0x14: lambda a, b: int(a == b),
    0x16: lambda a, b: a & b,
    0x17: lambda a, b: a | b,
    0x18: lambda a, b: a ^ b,
    0x1A: lambda a, b: (b >> (248 - 8 * a)) & 0xFF if a < 32 else 0,
    0x1B: lambda a, b: b << a if a < 256 else 0,
    0x1C: lambda a, b: b >> a if a < 256 else 0,
    0x1D: lambda a, b: (_signed(b) >> a) if a < 256 else (MASK if b >> 255 else 0),
}

# the arithmetic whose wrap SWC-101 names
_WRAPS = {
    0x01: lambda a, b: a + b >= WORD,
    0x02: lambda a, b: a * b >= WORD,
    0x03: lambda a, b: a < b,
}


class Trace:
    """What one transaction did at the pcs the oracles ask about."""

    def __init__(self):
        self.wrapped: set = set()  # pcs whose ADD/MUL/SUB wrapped
        self.selfdestructs: set = set()
        self.origin_jumpis: set = set()
        self.reverted = False


def execute(frame: Frame, trace: Trace) -> Tuple[bytes, bool]:
    """Run a frame to its end; returns (output, selfdestructed). A
    reverting frame raises _Revert."""
    code, pc, steps = frame.code, 0, 0
    while pc < len(code):
        steps += 1
        if steps > STEP_LIMIT:
            raise _Revert("step limit")
        op = code[pc]
        if 0x60 <= op <= 0x7F:
            n = op - 0x5F
            frame.push(int.from_bytes(code[pc + 1 : pc + 1 + n].ljust(n, b"\x00"), "big"))
            pc += 1 + n
            continue
        if 0x80 <= op <= 0x8F:
            idx = op - 0x7F
            if len(frame.stack) < idx:
                raise _Revert("stack underflow")
            frame.push(*frame.stack[-idx])
        elif 0x90 <= op <= 0x9F:
            idx = op - 0x8F
            if len(frame.stack) <= idx:
                raise _Revert("stack underflow")
            frame.stack[-1], frame.stack[-1 - idx] = frame.stack[-1 - idx], frame.stack[-1]
        elif op in _BINARY:
            (a, ta), (b, tb) = frame.pop(), frame.pop()
            if op in _WRAPS and _WRAPS[op](a, b):
                trace.wrapped.add(pc)
            frame.push(_BINARY[op](a, b), ta or tb)
        elif op == 0x00:
            return b"", False
        elif op in (0x08, 0x09):
            (a, ta), (b, tb), (n, tn) = frame.pop(), frame.pop(), frame.pop()
            r = 0 if n == 0 else ((a + b) % n if op == 0x08 else (a * b) % n)
            frame.push(r, ta or tb or tn)
        elif op == 0x15:
            a, ta = frame.pop()
            frame.push(int(a == 0), ta)
        elif op == 0x19:
            a, ta = frame.pop()
            frame.push(~a & MASK, ta)
        elif op == 0x20:
            (off, _), (size, _) = frame.pop(), frame.pop()
            frame.push(int.from_bytes(keccak256(bytes(frame.mem(off, size))), "big"))
        elif op == 0x30:
            frame.push(frame.address)
        elif op == 0x32:
            frame.push(frame.origin, True)
        elif op == 0x33:
            frame.push(frame.caller)
        elif op == 0x34:
            frame.push(frame.value)
        elif op == 0x35:
            off, _ = frame.pop()
            chunk = frame.calldata[off : off + 32] if off < len(frame.calldata) else b""
            frame.push(int.from_bytes(chunk.ljust(32, b"\x00"), "big"))
        elif op == 0x36:
            frame.push(len(frame.calldata))
        elif op in (0x37, 0x39):
            (dst, _), (src, _), (size, _) = frame.pop(), frame.pop(), frame.pop()
            data = frame.calldata if op == 0x37 else frame.code
            chunk = data[src : src + size] if src < len(data) else b""
            frame.mem_write(dst, chunk.ljust(size, b"\x00"))
        elif op == 0x38:
            frame.push(len(frame.code))
        elif op == 0x50:
            frame.pop()
        elif op == 0x51:
            off, _ = frame.pop()
            frame.push(int.from_bytes(frame.mem(off, 32), "big"))
        elif op == 0x52:
            (off, _), (v, _) = frame.pop(), frame.pop()
            frame.mem_write(off, v.to_bytes(32, "big"))
        elif op == 0x53:
            (off, _), (v, _) = frame.pop(), frame.pop()
            frame.mem_write(off, bytes([v & 0xFF]))
        elif op == 0x54:
            key, _ = frame.pop()
            frame.push(frame.storage.get(key, 0))
        elif op == 0x55:
            (key, _), (v, _) = frame.pop(), frame.pop()
            frame.storage[key] = v
        elif op in (0x56, 0x57):
            dest, _ = frame.pop()
            if op == 0x57:
                cond, taint = frame.pop()
                if taint:
                    trace.origin_jumpis.add(pc)
                if not cond:
                    pc += 1
                    continue
            if dest not in frame.jumpdests:
                raise _Revert("bad jump destination")
            pc = dest
            continue
        elif op == 0x58:
            frame.push(pc)
        elif op == 0x59:
            frame.push(len(frame.memory))
        elif op == 0x5B:
            pass
        elif op == 0xF3:
            (off, _), (size, _) = frame.pop(), frame.pop()
            return bytes(frame.mem(off, size)), False
        elif op == 0xFD:
            raise _Revert("REVERT")
        elif op == 0xFE:
            raise _Revert("INVALID")
        elif op == 0xFF:
            frame.pop()
            trace.selfdestructs.add(pc)
            return b"", True
        else:
            raise ReplayError("opcode 0x%02x at pc %d is outside the reference" % (op, pc))
        pc += 1
    return b"", False


def _hex(value: str) -> bytes:
    value = (value or "").strip()
    if value.startswith(("0x", "0X")):
        value = value[2:]
    return bytes.fromhex(value)


def _int(value) -> int:
    if isinstance(value, int):
        return value
    return int(value, 16) if value else 0


class Replay:
    """A witness run on the reference: per message call, its trace."""

    def __init__(self, creator: int, calls: List[Tuple[int, Trace]]):
        self.creator = creator
        self.calls = calls


def replay(tx_sequence: dict, creation_hex: str) -> Replay:
    """Deploy ``creation_hex`` as the witness's first step says, then
    run every later step against the created contract."""
    steps = (tx_sequence or {}).get("steps") or []
    if len(steps) < 2:
        raise ReplayError("a witness needs a creation and a message call")
    creation = _hex(creation_hex)
    first = steps[0]
    if first.get("address"):
        raise ReplayError("the first step is not a contract creation")
    ctor_input = _hex(first.get("input"))
    if not ctor_input.startswith(creation):
        raise ReplayError("the creation step does not deploy the analysed code")
    creator = _int(first.get("origin"))
    callee = _int(steps[1].get("address"))
    storage: Dict[int, int] = {}
    frame = Frame(creation, ctor_input[len(creation):], creator, creator,
                  _int(first.get("value")), callee, storage)
    try:
        runtime, _ = execute(frame, Trace())
    except _Revert as e:
        raise ReplayError("the creation reverted: %s" % e)
    calls: List[Tuple[int, Trace]] = []
    alive = True
    for step in steps[1:]:
        if _int(step.get("address")) != callee:
            raise ReplayError("a step calls an account the witness did not create")
        sender = _int(step.get("origin"))
        trace = Trace()
        if alive and runtime:
            snapshot = dict(storage)
            frame = Frame(runtime, _hex(step.get("input")), sender, sender,
                          _int(step.get("value")), callee, storage)
            try:
                _, destroyed = execute(frame, trace)
                alive = not destroyed
            except _Revert:
                storage.clear()
                storage.update(snapshot)
                trace.reverted = True
        calls.append((sender, trace))
    return Replay(creator, calls)


def confirms(issue: dict, runtime_hex: str, rep: Replay) -> Optional[str]:
    """None when the replay shows the issue's hazard at its pc, else why not."""
    swc = str(issue.get("swc-id"))
    pc = issue.get("address")
    runtime = _hex(runtime_hex)
    if not isinstance(pc, int) or not 0 <= pc < len(runtime):
        return "pc %r lies outside the runtime code" % (pc,)
    op = runtime[pc]
    if swc == "101":
        if op not in _WRAPS:
            return "pc %d holds 0x%02x, not ADD/MUL/SUB" % (pc, op)
        if any(pc in t.wrapped and not t.reverted for _, t in rep.calls):
            return None
        return "the arithmetic at pc %d never wrapped in a transaction that did not revert" % pc
    if swc == "106":
        if op != 0xFF:
            return "pc %d holds 0x%02x, not SELFDESTRUCT" % (pc, op)
        if any(pc in t.selfdestructs and not t.reverted and s != rep.creator
               for s, t in rep.calls):
            return None
        return "no transaction of another sender ran the SELFDESTRUCT at pc %d" % pc
    if swc == "115":
        if op != 0x57:
            return "pc %d holds 0x%02x, not JUMPI" % (pc, op)
        if any(pc in t.origin_jumpis for _, t in rep.calls):
            return None
        return "the JUMPI at pc %d never branched on ORIGIN" % pc
    return "SWC-%s has no oracle in the reference" % swc
