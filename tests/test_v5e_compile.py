"""Compile for a TPU v5e without one.

libtpu can describe a ``v5e:2x2`` slice it is not attached to
(``jax.experimental.topologies``), and XLA:TPU and Mosaic will lower and
compile for those devices. That is enough to catch what CPU tests cannot:
interpret mode turns a Pallas kernel into plain HLO, which GSPMD partitions
happily and which has no VMEM, so a kernel that overflows the chip's 16 MB of
scoped VMEM, or a Mosaic call left for GSPMD to partition, passes every
interpret-mode test and fails on the first chip.

The compiles run in child processes (``python tests/test_v5e_compile.py``
prints a verdict per program — a builder can run it by hand, all programs in
one process): the test process has JAX pinned to its CPU configuration. The
``verdict`` fixture starts ``COMPILE_CHILDREN`` of them side by side, each
compiling every n-th program (``python tests/test_v5e_compile.py I N``), with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``: without it two processes loading libtpu at
once collide on its lock file. (Threads of one process were tried first, PR
50: one run in five died of a stack overflow inside the compiler.)
"""

import concurrent.futures
import hashlib
import itertools
import json
import math
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The serve programs compiled whole: gpt2-medium's width (16 heads of 64 fold
# to 1024 lanes) and gpt2-xl's (25 x 64 = 1600, not a multiple of 128), depth
# cut; the benchmark's pool, the larger one PR 23 found a cliff at (an operand
# XLA would not update in place became a copy of the pool a call) and one
# well past it.
SERVE_WIDTHS = {"medium": "gpt2_medium", "xl": "gpt2_xl"}
SERVE_LAYERS, SERVE_SLOTS, SERVE_POOLS = 2, 36, (1281, 1537, 2049)

# LongCat-Flash at the sizes of the cell longcat-flash-omni.moe-decode:
# published widths, 4 layers, 16 experts held, 128 slots, pool 7297 x 16.
LONGCAT_SLOTS, LONGCAT_POOL = 128, 7297

# Olmo-Hybrid at the sizes of the cell olmo-hybrid-7b.hybrid-decode:
# published widths, 16 layers (12 linear + 4 full), 48 slots, pool 3265 x 16,
# the 2048 bucket.
OLMO_SLOTS, OLMO_POOL, OLMO_BUCKET = 48, 3265, 2048


# Falcon-H1 at the sizes of the cell falcon-h1-34b.ssm-decode: published
# widths, 6 layers (each a Mamba-2 mixer beside 20:4 grouped-query attention),
# 64 slots, pool 3905 x 16, the 1024 bucket (the largest).
FALCON_SLOTS, FALCON_POOL, FALCON_BUCKET = 64, 3905, 1024


# Kimi-K2.5 at the sizes of the cell kimi-k2.5.agent-decode: published
# widths, the dense layer and six expert layers, 12 experts held, 96 slots,
# pool 16993 x 16, the 2048 bucket and the 3072 one (the largest program).
KIMI_SLOTS, KIMI_POOL, KIMI_BUCKETS = 96, 16993, (2048, 3072)


# Trinity-Large-Preview at the sizes of the cell
# trinity-large-preview.window-decode: published widths, one dense and four
# expert layers (four sliding, one full), 16 experts held, 64 slots, pool
# 30785 x 16 for the ONE full layer, the 8192 bucket (the largest).
TRINITY_SLOTS, TRINITY_POOL, TRINITY_BUCKET = 64, 30785, 8192


# MiMo-V2-Flash at the sizes of the cell mimo-v2-flash.swa-decode: published
# widths, the first seven layers (two full, five window), 8 experts held, 128
# slots, pool 32769 x 16 for the TWO full layers, the 4096 bucket (the
# largest).
MIMO_SLOTS, MIMO_POOL, MIMO_BUCKET = 128, 32769, 4096


# GLM-5 at the sizes of the cell glm-5.sparse-decode: published widths, one
# dense and four expert layers, 8 experts held, 48 slots, pool 21937 x 16 in
# BOTH pool arrays (latent rows and index keys), the 8192 bucket (the
# largest).
GLM_SLOTS, GLM_POOL, GLM_BUCKET = 48, 21937, 8192
# The per-layer metrics that find an operation of the selection by its name
# (a Pallas kernel) or, where XLA runs it, by its kind and shape: the decode
# step's, then the prefill's.
DSA_METRICS = ("dsa_index_ms_per_step.batch", "dsa_select_ms_per_step.batch",
               "dsa_gather_ms_per_step.batch", "sparse_attn_roofline")
DSA_PREFILL_METRICS = ("dsa_index_ms_per_prefill.batch",
                       "dsa_select_ms_per_prefill.batch",
                       "mla_attn_ms_per_prefill.batch")


# Nemotron-3-Nano-30B-A3B at the sizes of the cell
# nemotron-3-nano-30b-a3b.reason-decode: published widths, 13 layers
# MEMEM*EMEMEM* (6 mixers, 5 expert layers of 64 held, 2 attention layers),
# 128 slots, pool 16513 x 16 for the TWO attention layers, the 2176 bucket
# (max_seq_len: the largest program the warm-up compiles).
NEMOTRON_SLOTS, NEMOTRON_POOL, NEMOTRON_BUCKET = 128, 16513, 2176

# LFM2-8B-A1B at the sizes of the cell lfm2-8b-a1b.conv-decode: published
# widths, the first 10 layers (8 convolution, 2 attention; 2 dense, 8 expert
# layers of all 32 experts), 128 slots, pool 16513 x 16 for the TWO
# attention layers, the 2176 bucket (max_seq_len: the largest program the
# warm-up compiles).
LFM2_SLOTS, LFM2_POOL, LFM2_BUCKET = 128, 16513, 2176
# The per-layer metrics that find the family's fusions by their output shapes.
LFM2_METRICS = ("lfm2_expert_ffn_roofline", "short_conv_roofline")

# The fixture's children: one compiled the 52 programs in 371 s alone and 538
# beside five busy workers, of a limit of 700.
COMPILE_CHILDREN = 4


# instruction_multiset() of three families' compiled decode programs on PR
# 47's tree (Nemotron-H's is in ``test_nemotron_decode_is_the_program_it_was``):
# they call the paged kernel without a row, and PR 48, which gave the GPT-2
# family's decode a kernel that takes one, left them what they were.
OLMO_DECODE = [8752, "4aca802a7068f431"]
FALCON_DECODE = [5598, "18bfcfbe696d2b2e"]
TRINITY_DECODE = [5994, "232c74127b5f0121"]


# An optimized module's Pallas calls: ``%<name>.N = <first output shape>...
# custom-call(...), custom_call_target="tpu_custom_call"``. The profiler names
# a device operation by this same text, and the benchmark's kernel metrics
# match ``<name>:custom-call:<shape>`` in it.
_KERNEL = re.compile(r"%([\w\-]+?)(?:\.\d+)* = \(?(\w+\[[\d,]*\])[^\n]*"
                     r"custom_call_target=\"tpu_custom_call\"")

# What a compiled Pallas call says it takes of scoped VMEM, in bytes.
_SCOPED = re.compile(r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                     r'"offset":"\d+","size":"(\d+)"')
V5E_SCOPED_VMEM = 16 << 20

# One instruction of an optimized module: name, output shape(s), op kind.
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w\-.]+) = (.*?) ([a-z][\w\-]*)\(")
# Op kinds that move no data: they name, pass on or alias a buffer.
_NO_DATA = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call", "optimization-barrier"}
# Op kinds that write part of a buffer where it lies.
_IN_PLACE = ("scatter", "dynamic-update-slice")


def pool_shaped_data_movers(hlo: str, n_layers: int, num_blocks: int,
                            block_tokens: int) -> list:
    """[[op kind, instruction, shape], ...]: every instruction of the
    optimized module whose output has the KV pool's shape, or one layer's
    slab of it, and that is not the in-place write: a ``scatter`` or a
    ``dynamic-update-slice``, the ``fusion`` that wraps one and aliases the
    pool through, or a Pallas call whose output aliases its operand. A
    ``copy``, a ``slice`` or any other fusion of that shape is pool-sized
    traffic that a serve program pays on every call. (The same scan serves a
    per-slot state ``[layers, slots, rows, ...]``: give it those numbers.)"""
    shapes = (f"[{n_layers},{num_blocks},{block_tokens},",
              f"[{num_blocks},{block_tokens},")
    bodies, current = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w\-.]+) \(.*\{\s*$", line)
        if head:
            current = bodies.setdefault(head.group(1), [])
        elif current is not None:
            current.append(line)
    found = []
    for body in bodies.values():
        for line in body:
            m = _INSTR.match(line)
            if not m or not any(s in m.group(2) for s in shapes):
                continue
            name, shape, op = m.groups()
            if op in _NO_DATA or op in _IN_PLACE:
                continue
            if op == "custom-call" and "output_to_operand_aliasing" in line:
                continue
            called = re.search(r"calls=%([\w\-.]+)", line)
            if op == "fusion" and called and any(
                    f" {write}(" in inner for write in _IN_PLACE
                    for inner in bodies.get(called.group(1), [])):
                continue
            found.append([op, name, shape[:80]])
    return found


def pool_writes(hlo: str, n_layers: int, num_blocks: int,
                block_tokens: int) -> list:
    """[[op kind, instruction], ...]: every ``scatter`` or
    ``dynamic-update-slice`` of the optimized module, inside a fusion or
    not, whose output is the KV pool: the program's own writes of K and V
    rows, each a pass of its own over HBM before the kernel that reads the
    row back."""
    shape = f"[{n_layers},{num_blocks},{block_tokens},"
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m.group(3) in _IN_PLACE and shape in m.group(2):
            found.append([m.group(3), m.group(1)])
    return found


def kernel_operand_shapes(hlo: str, kernel: str) -> list:
    """The operand shapes (``operand_layout_constraints``) of the calls of
    the Pallas kernel ``kernel`` in the optimized module, each distinct list
    once."""
    found = []
    for line in hlo.splitlines():
        if (re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", line)
                and "tpu_custom_call" in line):
            shapes = re.findall(r"(\w+\[[\d,]*\])\{", re.search(
                r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
            if shapes not in found:
                found.append(shapes)
    return found


def kernel_aliases(hlo: str, kernel: str) -> list:
    """[[[output, operand], ...], ...]: for each call of the Pallas kernel
    ``kernel`` in the optimized module, the outputs that ARE one of its
    operands' buffers (``output_to_operand_aliasing``)."""
    found = []
    for line in hlo.splitlines():
        if (re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", line)
                and "tpu_custom_call" in line):
            found.append([[int(o), int(i)] for o, i in re.findall(
                r"\{(\d+)\}: \((\d+), \{\}\)", line)])
    return found


# What the TPU compiler's own prefetch of an operand into VMEM (memory space
# 1) looks like: an asynchronous copy or slice, and the call that joins the
# slices. It stands in for the read the product would make, once.
_PREFETCH = ("copy-done", "slice-done", "async-done")
# Op kinds that write what they read, in another type, order or cut.
_MOVES = {"convert", "copy", "transpose", "slice", "dynamic-slice",
          "reshape", "concatenate"}


def weight_shapes(*trees) -> list:
    """The shapes a weight matrix of these parameter trees can have in a
    program: each leaf of a million elements or more as it is, one layer of
    a stacked one, and each of those folded to two dimensions."""
    shapes = set()
    for tree in trees:
        for leaf in tree:
            shape = tuple(leaf)
            if math.prod(shape) < 1 << 20:
                continue
            forms = {shape}
            if len(shape) > 2:
                forms |= {shape[1:], (1,) + shape[1:]}
            for form in list(forms):
                if len(form) > 2:
                    forms |= {(form[0], math.prod(form[1:])),
                              (math.prod(form[:-1]), form[-1])}
            shapes |= {f for f in forms if math.prod(f) >= 1 << 20}
    return sorted(shapes)


def _computations(hlo: str):
    """({computation: [(line, name, output shape(s), op kind), ...]}, the
    names of the computations that are fusions' bodies) of an optimized
    module; its entry computation goes by ``ENTRY``."""
    bodies, current = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w\-.]+) \(.*\{\s*$", line)
        if head:
            current = bodies.setdefault(
                "ENTRY" if head.group(1) else head.group(2), [])
        elif current is not None and _INSTR.match(line):
            current.append((line, *_INSTR.match(line).groups()))
    fused = {called for body in bodies.values() for line, *_ in body
             for called in re.findall(r"fusion\(.*calls=%([\w\-.]+)", line)}
    return bodies, fused


def weight_shaped_data_movers(hlo: str, shapes) -> list:
    """[[op kind, instruction, shape, computation], ...]: every instruction
    of the optimized module, outside its fusions' bodies, that only MOVES
    data and writes an array of a weight's shape: a ``convert``, a ``copy``,
    a ``transpose``, a ``slice``, or a fusion of nothing but such
    (``slice_bitcast_fusion``). A serve program that is handed its weights
    in the form its products read has none: each is a weight's bytes read
    and written again on every call (``ENTRY``) or every token step (a
    loop's body). Not counted: the compiler's prefetch of an operand into
    VMEM (``S(1)`` in the output's layout, by an asynchronous copy or slice
    and the ``ConcatBitcast`` that joins the slices), which is the product's
    own read made early."""
    wanted = ["[" + ",".join(map(str, shape)) + "]" for shape in shapes]
    bodies, fused = _computations(hlo)
    found = []
    for computation, body in bodies.items():
        if computation in fused:
            continue
        for line, name, shape, op in body:
            if not any(w in shape for w in wanted):
                continue
            if op == "fusion":
                called = re.search(r"calls=%([\w\-.]+)", line).group(1)
                moves = all(inner in _MOVES or inner in _NO_DATA
                            for *_, inner in bodies[called])
            else:
                moves = op in _MOVES or op in _PREFETCH
            prefetch = "S(1)" in shape and (
                op in _PREFETCH or '"ConcatBitcast"' in line)
            if moves and not prefetch:
                found.append([op, name, shape[:80], computation])
    return found


def pair_row_arrays(hlo: str, n_tokens: int, topk: int, widths) -> list:
    """[[op kind, instruction, shape, computation], ...]: every instruction
    of the optimized module, outside its fusions' bodies, that writes an
    array of ALL a call's (token, pick) pairs' rows, ``[n_tokens * topk, w]``
    or ``[n_tokens, topk, w]`` with ``w`` a width of the expert layer (the
    model's, an expert's or twice that). ``ops/moe.py:held_experts_ffn``
    under its row bound leaves none: its buffer is the chip's share of the
    pairs, and the combine sums a window's rows by token."""
    wanted = [f"[{n_tokens * topk},{w}]" for w in widths] + [
        f"[{n_tokens},{topk},{w}]" for w in widths]
    found = []
    bodies, fused = _computations(hlo)
    for computation, body in bodies.items():
        if computation in fused:
            continue
        for _line, name, shape, op in body:
            if op not in _NO_DATA and any(w in shape for w in wanted):
                found.append([op, name, shape[:80], computation])
    return found


def expert_widths(hidden: int, expert_ffn: int) -> tuple:
    """Row widths of an expert layer's arrays: the model's, gate | up's, one
    of the two's."""
    return (hidden, 2 * expert_ffn, expert_ffn)


def flash_layout_movers(hlo: str, heads: int, seq: int,
                        head_dim: int) -> list:
    """[[op kind, instruction, shape], ...]: what carrying ``[B, L, H, D]``
    to a flash kernel's own layout and back leaves in an optimized module
    (``_fold`` / ``_unfold``, until PR 41). (1) Every instruction outside the
    fusions' bodies that writes a bfloat16 array ``[b, heads, seq,
    head_dim]``: the projections' products labelled head-major and the
    copies that turned them. (2) Every ``copy`` or ``transpose``, or fusion
    of nothing but such, that a flash kernel reads an operand from or that
    reads a flash kernel's result, through bitcasts and tuple elements: a
    kernel that asks for a layout XLA does not write (``[B, L, H*D]``
    row-major was one) is handed copies whatever their shape. Only
    bfloat16 arrays count: q, k, v, o and their gradients; the float32
    statistics are a 4 KB row a head, and a ``copy`` of those is the
    compiler's prefetch into VMEM. A kernel that takes the array where the
    projections leave it has neither."""
    head_major = re.compile(rf"^\(?bf16\[\d+,{heads},{seq},{head_dim}\]")
    bodies, instrs, current = {}, {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w\-.]+) \(.*\{\s*$", line)
        if head:
            current = bodies.setdefault(head.group(1), [])
        elif current is not None and _INSTR.match(line):
            name, shape, op = _INSTR.match(line).groups()
            args = line.split(f" {op}(", 1)[1].split(")", 1)[0]
            current.append(name)
            instrs[name] = (op, shape, re.findall(r"%([\w\-.]+)", args),
                            line)
    fused = {called for _op, _shape, _args, line in instrs.values()
             for called in re.findall(r"fusion\(.*calls=%([\w\-.]+)", line)}

    def source(name):
        while name in instrs and instrs[name][0] in ("bitcast",
                                                     "get-tuple-element"):
            name = instrs[name][2][0]
        return name

    def moves(name):
        op, shape, _args, line = instrs.get(name, ("", "", [], ""))
        if not shape.lstrip("(").startswith("bf16["):
            return False
        if op == "fusion":
            called = re.search(r"calls=%([\w\-.]+)", line).group(1)
            return all(instrs[inner][0] in _MOVES
                       or instrs[inner][0] in _NO_DATA
                       for inner in bodies[called])
        return op in ("copy", "transpose")

    def is_flash(name):
        return (name.startswith("flash_") and name in instrs
                and "tpu_custom_call" in instrs[name][3])

    found = set()
    for computation, names in bodies.items():
        if computation in fused:
            continue
        for name in names:
            op, shape, args, _line = instrs[name]
            if head_major.match(shape) and op not in _NO_DATA:
                found.add(name)
            if is_flash(name):
                found |= {source(a) for a in args if moves(source(a))}
            elif moves(name) and any(is_flash(source(a)) for a in args):
                found.add(name)
    return sorted([instrs[n][0], n, instrs[n][1][:60]] for n in found)


def instruction_multiset(hlo: str) -> list:
    """[instructions, sha256 of the sorted (op kind, output shape) of every
    one]: an optimized module's instructions whatever their names and order
    (the fusions' bodies included). Two compiles of one traced program give
    the same pair; a program whose products moved, or whose fusions were cut
    otherwise, does not."""
    bodies, _fused = _computations(hlo)
    found = sorted(f"{op} {shape}" for body in bodies.values()
                   for _line, _name, shape, op in body)
    return [len(found),
            hashlib.sha256("\n".join(found).encode()).hexdigest()[:16]]


def named_ops(hlo: str, pattern: str) -> list:
    """[[name, ``op_name`` metadata], ...] of the instructions outside the
    fusions' bodies whose name, as the profiler gives it and the benchmark
    shortens it (``reduce/trace.py:short_name``), matches ``pattern``: what a
    metric file's ``pattern`` would count in this program's device trace."""
    from benchmark.reduce.trace import short_name

    fused = set(re.findall(r"fusion\(.*calls=%([\w\-.]+)", hlo))
    found, current = [], None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w\-.]+) \(.*\{\s*$", line)
        if head:
            current = head.group(2)
        elif current not in fused and _INSTR.match(line):
            name = short_name(line.strip().removeprefix("ROOT "))
            if re.search(pattern, name):
                scope = re.search(r'op_name="([^"]*)"', line)
                found.append([name, scope.group(1) if scope else ""])
    return found


def _inner_jaxprs(eqn):
    """The jaxprs an equation carries in its params (a jit's, a loop's)."""
    for sub in eqn.params.values():
        for inner in sub if isinstance(sub, (list, tuple)) else [sub]:
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def pallas_grids(jaxpr) -> list:
    """The grid of every ``pallas_call`` in a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(list(eqn.params["grid_mapping"].grid))
        for inner in _inner_jaxprs(eqn):
            found.extend(pallas_grids(inner))
    return found


def pallas_prefetched(jaxpr) -> dict:
    """{kernel name: the counts of scalar-prefetch operands its
    ``pallas_call``s take, sorted and distinct}, nested calls included."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], set()).add(
                eqn.params["grid_mapping"].num_index_operands)
        for inner in _inner_jaxprs(eqn):
            for name, counts in pallas_prefetched(inner).items():
                found.setdefault(name, set()).update(counts)
    return {name: sorted(counts) for name, counts in found.items()}


def pallas_products(jaxpr, prefix: str) -> dict:
    """{kernel name: [[lhs dtype, rhs dtype, output dtype], ...]}: every
    ``dot_general`` inside the ``pallas_call``s whose name starts with
    ``prefix``, loops and branches of the kernel included."""
    def products(inner):
        found = []
        for eqn in inner.eqns:
            if eqn.primitive.name == "dot_general":
                found.append([str(v.aval.dtype)
                              for v in (*eqn.invars, *eqn.outvars)])
            for sub in _inner_jaxprs(eqn):
                found.extend(products(sub))
        return found

    found = {}
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"].startswith(prefix)):
            found.setdefault(eqn.params["name"], []).extend(
                products(eqn.params["jaxpr"]))
        for inner in _inner_jaxprs(eqn):
            for name, dots in pallas_products(inner, prefix).items():
                found.setdefault(name, []).extend(dots)
    return found


def jit_calls(jaxpr, name: str) -> list:
    """The traced body (its ``id``) of every call of the jitted function
    ``name`` in a jaxpr, nested ones included: a call is a ``jit`` equation
    of that name, and calls that share one body are lowered once."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit" and eqn.params["name"] == name:
            found.append(id(eqn.params["jaxpr"]))
        for inner in _inner_jaxprs(eqn):
            found.extend(jit_calls(inner, name))
    return found


def compile_all(share: int = 0, of: int = 1) -> dict:
    """Child side, for the programs whose place in the list below is
    ``share`` modulo ``of``: {"skip": reason} or
    {"programs": {name: "ok" | error},
    "kernels": {name: [[instruction name, first output shape], ...]},
    "grids": {name: pallas_grids() of the traced program},
    "prefetched": {name: pallas_prefetched() of the traced program},
    "scoped_vmem": {name: bytes of scoped VMEM each Pallas call takes},
    "pool_movers": {serve program: pool_shaped_data_movers() of it},
    "temp_bytes": {serve program: temporaries the compiler reports},
    "need_bytes": {LongCat or Olmo serve program: arguments + temporaries},
    "state_movers": {Olmo serve program: the same scan for its slot state},
    "weight_movers": {serve program: weight_shaped_data_movers() of it},
    "pair_rows": {expert family's serve program: pair_row_arrays() of it},
    "state_roundings": {Olmo serve program: [calls of the state kernel,
    ``reduce-precision`` instructions that feed them]},
    "shared_expert_ops": {Kimi serve program: named_ops() of the pattern of
    ``shared_expert_ms_per_step.batch``},
    "capacity_ops": {Nemotron decode: named_ops() of the pattern of
    ``expert_capacity_ffn_roofline``},
    "dsa_ops": {GLM-5 serve program: {metric of DSA_METRICS: named_ops() of
    its pattern}},
    "lfm2_ops": {LFM2 decode: {metric of LFM2_METRICS: named_ops() of its
    pattern}},
    "multisets": {name: instruction_multiset() of the compiled program},
    "latent_calls": {name: [calls of the latent kernel's jit, distinct
    traced bodies among them]},
    "flash_products": {name: pallas_products() of the flash kernels},
    "flash_movers": {name: flash_layout_movers() of a program that runs them},
    "latent_vmem": {name: scoped VMEM of each latent kernel call},
    "pool_writes": {serve program: pool_writes() of it},
    "window_operands": {serve program: kernel_operand_shapes() of its window
    layers' decode kernel},
    "pool_aliases": {serve program: kernel_aliases() of its decode kernel}}."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding

    try:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu").devices
    except Exception as e:  # noqa: BLE001 — no libtpu, or it gives no topology
        return {"skip": f"no compile-only v5e topology: {type(e).__name__}: "
                        f"{str(e)[:300]}"}

    from ray_tpu.models import transformer
    from ray_tpu.models.generate import PagedGenerator
    from ray_tpu.models.training import make_train_step
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.paged_attention import paged_attention
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.serve.llm import _default_buckets

    # GPT-2-124M's width; the train step's depth is cut (compile time), the
    # refusals this test exists for do not depend on it.
    cfg = transformer.gpt2_small(max_seq_len=1024, n_layers=2, remat=True)
    ctx, H, D, bt, slots = cfg.max_seq_len, cfg.n_heads, cfg.head_dim, 16, 8
    one = SingleDeviceSharding(devices[0])
    programs, kernels, pool_movers, temp_bytes = {}, {}, {}, {}
    need_bytes, grids, scoped_vmem, state_movers = {}, {}, {}, {}
    state_roundings, weight_movers, shared_expert_ops = {}, {}, {}
    latent_calls, latent_vmem, flash_products, flash_movers = {}, {}, {}, {}
    pair_rows, pool_writers, pool_aliases = {}, {}, {}
    window_operands, prefetched = {}, {}
    with open(os.path.join(REPO, "benchmark", "metrics",
                           "shared_expert_ms_per_step.batch.json")) as f:
        shared_pattern = json.load(f)["pattern"]

    with open(os.path.join(REPO, "benchmark", "metrics",
                           "expert_capacity_ffn_roofline.json")) as f:
        capacity_pattern = json.load(f)["pattern"]
    capacity_ops, multisets = {}, {}
    dsa_patterns, dsa_ops = {}, {}
    for metric in DSA_METRICS + DSA_PREFILL_METRICS:
        with open(os.path.join(REPO, "benchmark", "metrics",
                               f"{metric}.json")) as f:
            dsa_patterns[metric] = json.load(f)["pattern"]
    lfm2_patterns, lfm2_ops = {}, {}
    for metric in LFM2_METRICS:
        with open(os.path.join(REPO, "benchmark", "metrics",
                               f"{metric}.json")) as f:
            lfm2_patterns[metric] = json.load(f)["pattern"]
    place = itertools.count()

    def attempt(name, trace, pool=None, state=None, weights=None,
                shared=False, state_kernel="gdn_decode", pairs=None,
                capacity=False, dsa=False, lfm2_fusions=False):
        if next(place) % of != share:
            return
        try:
            traced = trace()
            grids[name] = pallas_grids(traced.jaxpr.jaxpr)
            prefetched[name] = pallas_prefetched(traced.jaxpr.jaxpr)
            flash_products[name] = pallas_products(traced.jaxpr.jaxpr,
                                                   "flash_")
            bodies = jit_calls(traced.jaxpr.jaxpr, "_latent_attention")
            latent_calls[name] = [len(bodies), len(set(bodies))]
            compiled = traced.lower().compile()
            text = compiled.as_text()
            kernels[name] = sorted(set(_KERNEL.findall(text)))
            if flash_products[name]:
                flash_movers[name] = flash_layout_movers(text, H, ctx, D)
            scoped_vmem[name] = [
                int(n) for line in text.splitlines()
                if "tpu_custom_call" in line for n in _SCOPED.findall(line)]
            latent_vmem[name] = [
                int(n) for line in text.splitlines()
                if "tpu_custom_call" in line and re.match(r"\s*%mla_", line)
                for n in _SCOPED.findall(line)]
            window_operands[name] = kernel_operand_shapes(
                text, "window_decode_attn")
            if pool is not None:
                pool_movers[name] = pool_shaped_data_movers(text, *pool)
                pool_writers[name] = pool_writes(text, *pool)
                pool_aliases[name] = kernel_aliases(text, "paged_decode_attn")
                mem = compiled.memory_analysis()
                temp_bytes[name] = mem.temp_size_in_bytes
                need_bytes[name] = (mem.argument_size_in_bytes
                                    + mem.temp_size_in_bytes)
            if weights is not None:
                weight_movers[name] = weight_shaped_data_movers(text, weights)
            if shared:
                shared_expert_ops[name] = named_ops(text, shared_pattern)
            if capacity:
                capacity_ops[name] = named_ops(text, capacity_pattern)
            if dsa:
                dsa_ops[name] = {metric: named_ops(text, pattern)
                                 for metric, pattern in dsa_patterns.items()}
            if lfm2_fusions:
                lfm2_ops[name] = {metric: named_ops(text, pattern)
                                  for metric, pattern in lfm2_patterns.items()}
            if pairs is not None:
                pair_rows[name] = pair_row_arrays(text, *pairs)
            multisets[name] = instruction_multiset(text)
            if state is not None:
                state_movers[name] = pool_shaped_data_movers(text, *state)
                state_roundings[name] = [
                    len(re.findall(rf"(?m)^\s*%{state_kernel}[.\d]* = [^\n]*"
                                   r"tpu_custom_call", text)),
                    sum(f"_{state_kernel}" in line
                        for line in text.splitlines()
                        if " reduce-precision(" in line)]
            programs[name] = "ok"
        except Exception as e:  # noqa: BLE001 — the verdict IS the result
            programs[name] = f"{type(e).__name__}: {str(e)[:600]}"

    def arr(shape, dtype=jnp.bfloat16, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def shapes_of(*trees):
        return weight_shapes(*([leaf.shape for leaf in jax.tree.leaves(tree)]
                               for tree in trees))

    qkv = arr((2, ctx, H, D))
    attempt("flash_fwd_bwd", lambda: jax.jit(jax.value_and_grad(
        lambda q, k, v: flash_attention(
            q, k, v, True, None, 512, 512, False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).trace(qkv, qkv, qkv))

    # Past 2,048 keys a head's K and V leave VMEM for spans on the grid.
    long_qkv = arr((1, 4 * ctx, 2, D))
    attempt("flash_fwd_bwd_long", lambda: jax.jit(jax.value_and_grad(
        lambda q, k, v: flash_attention(
            q, k, v, True, None, 4 * ctx, 4 * ctx,
            False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).trace(long_qkv, long_qkv, long_qkv))

    nb_seq = ctx // bt
    pool = arr((1, 2 * slots * nb_seq + 1, bt, H * D))   # one layer's: [None]
    cases = [("paged_decode", slots, 1), ("paged_verify", slots, 5)]
    cases += [(f"paged_prefill_{b}", 1, b) for b in _default_buckets(ctx)]
    for name, s, t in cases:
        attempt(name, lambda s=s, t=t: jax.jit(paged_attention).trace(
            arr((s, t, H, D)), pool, pool, arr((s, nb_seq), jnp.int32),
            arr((s,), jnp.int32), arr((), jnp.int32)))

    # The kernel alone at the serve cells' geometry (36 slots, a table of 64,
    # the 1024 bucket in tiles of 128), both published widths, both pools.
    for width, full in SERVE_WIDTHS.items():
        wcfg = getattr(transformer, full)(max_seq_len=ctx)
        for num_blocks in SERVE_POOLS:
            wpool = arr((SERVE_LAYERS, num_blocks, bt,
                         wcfg.n_heads * wcfg.head_dim))
            for kind, s, t in (("decode", SERVE_SLOTS, 1), ("prefill", 1, ctx)):
                attempt(f"kernel_{kind}_{width}_{num_blocks}",
                        lambda s=s, t=t, c=wcfg, wpool=wpool: jax.jit(
                            paged_attention).trace(
                            arr((s, t, c.n_heads, c.head_dim)), wpool, wpool,
                            arr((s, nb_seq), jnp.int32), arr((s,), jnp.int32),
                            arr((), jnp.int32)),
                        pool=(SERVE_LAYERS, num_blocks, bt))

    # The serve programs whole, at the benchmark's serve geometry (36 slots,
    # blocks of 16, the decode chunk of 8, the 256 bucket) and two published
    # widths, depth cut: what XLA does with the pool AROUND the kernel.
    for width, full in SERVE_WIDTHS.items():
        scfg = getattr(transformer, full)(max_seq_len=ctx).replace(
            n_layers=SERVE_LAYERS)
        # The generator makes its working tree when it is built, so it is
        # built from a real (zero) stored tree; the programs are traced with
        # the working tree's shapes, placed on the described chip.
        stored = jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype),
            jax.eval_shape(lambda key, c=scfg: transformer.init_params(c, key),
                           jax.random.key(0)))
        for num_blocks in SERVE_POOLS:
            gen = PagedGenerator(stored, scfg, slots=SERVE_SLOTS,
                                 num_blocks=num_blocks, block_tokens=bt,
                                 attention_kernel="pallas")
            params = jax.tree.map(lambda x: arr(x.shape, x.dtype), gen.params)
            weights = shapes_of(stored, params)
            kv = arr((scfg.n_layers, num_blocks, bt,
                      scfg.n_heads * scfg.head_dim))
            state = (params, kv, kv, arr((SERVE_SLOTS, gen.logits_dim),
                                         jnp.float32),
                     arr((SERVE_SLOTS, 2), jnp.uint32))
            per_slot = lambda dtype: arr((SERVE_SLOTS,), dtype)  # noqa: E731
            i32 = arr((), jnp.int32)
            geometry = (scfg.n_layers, num_blocks, bt)
            # the pool: one pytree; the slot state: none for this family
            state = (params, (kv, kv), ()) + state[3:]
            attempt(f"serve_decode_{width}_{num_blocks}",
                    lambda: gen.decode_fn(8).trace(
                        *state, arr((SERVE_SLOTS, gen.blocks_per_seq),
                                    jnp.int32),
                        per_slot(jnp.int32), per_slot(jnp.bool_),
                        per_slot(jnp.bool_), per_slot(jnp.float32)),
                    pool=geometry, weights=weights)
            attempt(f"serve_prefill_{width}_{num_blocks}",
                    lambda: gen.prefill_fn(256).trace(
                        *state, arr((gen.blocks_per_seq,), jnp.int32),
                        arr((1, 256), jnp.int32), i32, i32, i32, i32),
                    pool=geometry, weights=weights)

    # LongCat-Flash's serve programs whole, at the cell's own sizes: the
    # latent kernels under their names, no pool-shaped copy, and the bytes
    # the chip must hold (5.2B bf16 parameters and a 1.2 GB latent pool).
    from ray_tpu.models import longcat

    lcfg = longcat.longcat_flash_share()
    lparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: longcat.init_params(lcfg, key),
                       jax.random.key(0)))
    lgen = PagedGenerator(lparams, lcfg, slots=LONGCAT_SLOTS,
                          num_blocks=LONGCAT_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    lstate = (lparams,
              (arr((lcfg.attn_sublayers, LONGCAT_POOL, bt, lcfg.pool_width)),),
              (), arr((LONGCAT_SLOTS, lgen.logits_dim), jnp.float32),
              arr((LONGCAT_SLOTS, 2), jnp.uint32))
    l_slot = lambda dtype: arr((LONGCAT_SLOTS,), dtype)  # noqa: E731
    i32 = arr((), jnp.int32)
    l_geometry = (lcfg.attn_sublayers, LONGCAT_POOL, bt)
    attempt("longcat_decode",
            lambda: lgen.decode_fn(8).trace(
                *lstate, arr((LONGCAT_SLOTS, lgen.blocks_per_seq), jnp.int32),
                l_slot(jnp.int32), l_slot(jnp.bool_), l_slot(jnp.bool_),
                l_slot(jnp.float32)), pool=l_geometry,
            weights=shapes_of(lparams))
    attempt("longcat_prefill_1024",
            lambda: lgen.prefill_fn(1024).trace(
                *lstate, arr((lgen.blocks_per_seq,), jnp.int32),
                arr((1, 1024), jnp.int32), i32, i32, i32, i32),
            pool=l_geometry, weights=shapes_of(lparams),
            pairs=(1024, lcfg.moe_topk, expert_widths(
                lcfg.hidden_size, lcfg.expert_ffn_hidden_size)))

    # Olmo-Hybrid's serve programs whole, at the cell's own sizes: the state
    # kernel under its name, the K/V pool AND the per-slot recurrent state
    # written where they lie, and the bytes the chip must hold (4.1B bf16
    # parameters, a 3.2 GB pool, 1.3 GB of slot state).
    from ray_tpu.models import olmo_hybrid

    ocfg = olmo_hybrid.olmo_hybrid_stage()
    oparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: olmo_hybrid.init_params(ocfg, key),
                       jax.random.key(0)))
    ogen = PagedGenerator(oparams, ocfg, slots=OLMO_SLOTS,
                          num_blocks=OLMO_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    okv = arr((ocfg.n_layers, OLMO_POOL, bt, ocfg.hidden_size))
    oslot = tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: olmo_hybrid.init_slot_state(ocfg, OLMO_SLOTS)))
    ostate = (oparams, (okv, okv), oslot,
              arr((OLMO_SLOTS, ogen.logits_dim), jnp.float32),
              arr((OLMO_SLOTS, 2), jnp.uint32))
    o_slot = lambda dtype: arr((OLMO_SLOTS,), dtype)  # noqa: E731
    o_geometry = (ocfg.n_layers, OLMO_POOL, bt)
    o_state = (ocfg.n_linear, OLMO_SLOTS, ocfg.linear_key_head_dim)
    attempt("olmo_decode",
            lambda: ogen.decode_fn(8).trace(
                *ostate, arr((OLMO_SLOTS, ogen.blocks_per_seq), jnp.int32),
                o_slot(jnp.int32), o_slot(jnp.bool_), o_slot(jnp.bool_),
                o_slot(jnp.float32)), pool=o_geometry, state=o_state,
            weights=shapes_of(oparams))
    attempt(f"olmo_prefill_{OLMO_BUCKET}",
            lambda: ogen.prefill_fn(OLMO_BUCKET).trace(
                *ostate, arr((ogen.blocks_per_seq,), jnp.int32),
                arr((1, OLMO_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=o_geometry, state=o_state, weights=shapes_of(oparams))

    # Falcon-H1's serve programs whole, at the cell's own sizes: the state
    # kernel under its name and its float32 operand, the grouped-query
    # attention kernel under the attention kernel's names on a pool row of
    # the KV heads, no weight re-laid on a call, and the bytes the chip must
    # hold (5.25B bf16 parameters, 1.6 GB of slot state, a 0.77 GB pool).
    from ray_tpu.models import falcon_h1

    fcfg = falcon_h1.falcon_h1_stage()
    fparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: falcon_h1.init_params(fcfg, key),
                       jax.random.key(0)))
    fgen = PagedGenerator(fparams, fcfg, slots=FALCON_SLOTS,
                          num_blocks=FALCON_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    fkv = arr((fcfg.n_layers, FALCON_POOL, bt, fcfg.n_kv_heads * fcfg.head_dim))
    fslot = tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: falcon_h1.init_slot_state(fcfg, FALCON_SLOTS)))
    fstate = (fparams, (fkv, fkv), fslot,
              arr((FALCON_SLOTS, fgen.logits_dim), jnp.float32),
              arr((FALCON_SLOTS, 2), jnp.uint32))
    f_slot = lambda dtype: arr((FALCON_SLOTS,), dtype)  # noqa: E731
    f_geometry = (fcfg.n_layers, FALCON_POOL, bt)
    f_state = (fcfg.num_hidden_layers, FALCON_SLOTS, fcfg.mamba_d_state)
    attempt("falcon_decode",
            lambda: fgen.decode_fn(8).trace(
                *fstate, arr((FALCON_SLOTS, fgen.blocks_per_seq), jnp.int32),
                f_slot(jnp.int32), f_slot(jnp.bool_), f_slot(jnp.bool_),
                f_slot(jnp.float32)), pool=f_geometry, state=f_state,
            weights=shapes_of(fparams), state_kernel="ssd_decode")
    attempt(f"falcon_prefill_{FALCON_BUCKET}",
            lambda: fgen.prefill_fn(FALCON_BUCKET).trace(
                *fstate, arr((fgen.blocks_per_seq,), jnp.int32),
                arr((1, FALCON_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=f_geometry, state=f_state, weights=shapes_of(fparams),
            state_kernel="ssd_decode")

    # Kimi-K2.5's serve programs whole, at the cell's own sizes: the latent
    # kernels under their names, no weight re-laid on a call, what the shared
    # expert's metric would count, and the bytes the chip must hold (4.85B
    # bf16 parameters and a 2.44 GB latent pool).
    from ray_tpu.models import kimi_k2

    kcfg = kimi_k2.kimi_k2_share()
    kparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: kimi_k2.init_params(kcfg, key),
                       jax.random.key(0)))
    kgen = PagedGenerator(kparams, kcfg, slots=KIMI_SLOTS,
                          num_blocks=KIMI_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    kstate = (kparams,
              (arr((kcfg.attn_sublayers, KIMI_POOL, bt, kcfg.pool_width)),),
              (), arr((KIMI_SLOTS, kgen.logits_dim), jnp.float32),
              arr((KIMI_SLOTS, 2), jnp.uint32))
    k_slot = lambda dtype: arr((KIMI_SLOTS,), dtype)  # noqa: E731
    k_geometry = (kcfg.attn_sublayers, KIMI_POOL, bt)
    k_widths = expert_widths(kcfg.hidden_size, kcfg.moe_intermediate_size)
    attempt("kimi_decode",
            lambda: kgen.decode_fn(8).trace(
                *kstate, arr((KIMI_SLOTS, kgen.blocks_per_seq), jnp.int32),
                k_slot(jnp.int32), k_slot(jnp.bool_), k_slot(jnp.bool_),
                k_slot(jnp.float32)), pool=k_geometry,
            weights=shapes_of(kparams), shared=True,
            pairs=(KIMI_SLOTS, kcfg.num_experts_per_tok, k_widths))
    for bucket in KIMI_BUCKETS:
        attempt(f"kimi_prefill_{bucket}",
                lambda bucket=bucket: kgen.prefill_fn(bucket).trace(
                    *kstate, arr((kgen.blocks_per_seq,), jnp.int32),
                    arr((1, bucket), jnp.int32), i32, i32, i32, i32),
                pool=k_geometry, shared=True,
                pairs=(bucket, kcfg.num_experts_per_tok, k_widths))

    # Trinity's serve programs whole, at the cell's own sizes: the window
    # layers' kernel under a name of its own beside the full layer's, on
    # rings viewed as blocks (no ring-sized copy), 48 query heads over a
    # 1,024-lane row, and the bytes the chip must hold (2.51B bf16
    # parameters, 4.43 GB of rings, a 2.02 GB pool).
    from ray_tpu.models import afmoe

    tcfg = afmoe.trinity_large_share()
    tparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: afmoe.init_params(tcfg, key),
                       jax.random.key(0)))
    tgen = PagedGenerator(tparams, tcfg, slots=TRINITY_SLOTS,
                          num_blocks=TRINITY_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    tkv = arr((tcfg.n_layers, TRINITY_POOL, bt, tcfg.n_kv_heads * tcfg.head_dim))
    tslot = tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: afmoe.init_slot_state(tcfg, TRINITY_SLOTS)))
    tstate = (tparams, (tkv, tkv), tslot,
              arr((TRINITY_SLOTS, tgen.logits_dim), jnp.float32),
              arr((TRINITY_SLOTS, 2), jnp.uint32))
    t_slot = lambda dtype: arr((TRINITY_SLOTS,), dtype)  # noqa: E731
    t_geometry = (tcfg.n_layers, TRINITY_POOL, bt)
    t_rings = (tcfg.window_layers, TRINITY_SLOTS, tcfg.ring_blocks)
    attempt("trinity_decode",
            lambda: tgen.decode_fn(8).trace(
                *tstate, arr((TRINITY_SLOTS, tgen.blocks_per_seq), jnp.int32),
                t_slot(jnp.int32), t_slot(jnp.bool_), t_slot(jnp.bool_),
                t_slot(jnp.float32)), pool=t_geometry, state=t_rings,
            weights=shapes_of(tparams))
    attempt(f"trinity_prefill_{TRINITY_BUCKET}",
            lambda: tgen.prefill_fn(TRINITY_BUCKET).trace(
                *tstate, arr((tgen.blocks_per_seq,), jnp.int32),
                arr((1, TRINITY_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=t_geometry, state=t_rings,
            pairs=(TRINITY_BUCKET, tcfg.num_experts_per_tok, expert_widths(
                tcfg.hidden_size, tcfg.moe_intermediate_size)))

    # MiMo-V2-Flash's serve programs whole, at the cell's own sizes: K rows
    # of 192 a head and V rows of 128 in a pool of 4 KV heads and rings of
    # 8, a sink a query head in the window layers' kernel, and the bytes the
    # chip must hold (2.22B bf16 parameters, a 2.68 GB pool, 0.63 GB of
    # rings).
    from ray_tpu.models import mimo_v2

    mcfg = mimo_v2.flash_share()
    mparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: mimo_v2.init_params(mcfg, key),
                       jax.random.key(0)))
    mgen = PagedGenerator(mparams, mcfg, slots=MIMO_SLOTS,
                          num_blocks=MIMO_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    mpool, mslot = (tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(f))
                    for f in (lambda: mimo_v2.init_pool(mcfg, MIMO_POOL, bt),
                              lambda: mimo_v2.init_slot_state(mcfg, MIMO_SLOTS)))
    mstate = (mparams, mpool, mslot,
              arr((MIMO_SLOTS, mgen.logits_dim), jnp.float32),
              arr((MIMO_SLOTS, 2), jnp.uint32))
    m_slot = lambda dtype: arr((MIMO_SLOTS,), dtype)  # noqa: E731
    m_geometry = (mcfg.n_layers, MIMO_POOL, bt)
    m_rings = (mcfg.window_layers, MIMO_SLOTS, mcfg.ring_blocks)
    attempt("mimo_decode",
            lambda: mgen.decode_fn(8).trace(
                *mstate, arr((MIMO_SLOTS, mgen.blocks_per_seq), jnp.int32),
                m_slot(jnp.int32), m_slot(jnp.bool_), m_slot(jnp.bool_),
                m_slot(jnp.float32)), pool=m_geometry, state=m_rings,
            weights=shapes_of(mparams))
    attempt(f"mimo_prefill_{MIMO_BUCKET}",
            lambda: mgen.prefill_fn(MIMO_BUCKET).trace(
                *mstate, arr((mgen.blocks_per_seq,), jnp.int32),
                arr((1, MIMO_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=m_geometry, state=m_rings,
            pairs=(MIMO_BUCKET, mcfg.num_experts_per_tok, expert_widths(
                mcfg.hidden_size, mcfg.moe_intermediate_size)))

    # GLM-5's serve programs whole, at the cell's own sizes: the selection's
    # kernel under its two names beside the latent kernel's (which takes the
    # keep bits), what the selection's metrics would count, and the bytes the
    # chip must hold (2.70B bf16 parameters and 3.78 GB in the two pools).
    from ray_tpu.models import glm_dsa

    gcfg = glm_dsa.glm_5_share()
    gparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: glm_dsa.init_params(gcfg, key),
                       jax.random.key(0)))
    ggen = PagedGenerator(gparams, gcfg, slots=GLM_SLOTS, num_blocks=GLM_POOL,
                          block_tokens=bt, attention_kernel="pallas")
    gpool = tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: glm_dsa.init_pool(gcfg, GLM_POOL, bt)))
    gstate = (gparams, gpool, (),
              arr((GLM_SLOTS, ggen.logits_dim), jnp.float32),
              arr((GLM_SLOTS, 2), jnp.uint32))
    g_slot = lambda dtype: arr((GLM_SLOTS,), dtype)  # noqa: E731
    g_geometry = (gcfg.attn_sublayers, GLM_POOL, bt)
    attempt("glm_decode",
            lambda: ggen.decode_fn(8).trace(
                *gstate, arr((GLM_SLOTS, ggen.blocks_per_seq), jnp.int32),
                g_slot(jnp.int32), g_slot(jnp.bool_), g_slot(jnp.bool_),
                g_slot(jnp.float32)), pool=g_geometry,
            weights=shapes_of(gparams), dsa=True)
    attempt(f"glm_prefill_{GLM_BUCKET}",
            lambda: ggen.prefill_fn(GLM_BUCKET).trace(
                *gstate, arr((ggen.blocks_per_seq,), jnp.int32),
                arr((1, GLM_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=g_geometry, dsa=True)

    # Nemotron-H's serve programs whole, at the cell's own sizes: a layer is
    # ONE thing, so the state kernel is called by the six mixer layers alone
    # on a float32 state of its own depth, the attention kernel by the two
    # attention layers alone on a pool of its own depth, the grouped product
    # by the five expert layers; no weight re-laid on a call, and the bytes
    # the chip must hold (3.93B bf16 parameters, 1.64 GB of slot state, a
    # 0.54 GB pool).
    from ray_tpu.models import nemotron_h

    ncfg = nemotron_h.nemotron_nano_share()
    nparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: nemotron_h.init_params(ncfg, key),
                       jax.random.key(0)))
    ngen = PagedGenerator(nparams, ncfg, slots=NEMOTRON_SLOTS,
                          num_blocks=NEMOTRON_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    nkv = arr((ncfg.n_layers, NEMOTRON_POOL, bt,
               ncfg.n_kv_heads * ncfg.head_dim))
    nslot = tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: nemotron_h.init_slot_state(ncfg, NEMOTRON_SLOTS)))
    nstate = (nparams, (nkv, nkv), nslot,
              arr((NEMOTRON_SLOTS, ngen.logits_dim), jnp.float32),
              arr((NEMOTRON_SLOTS, 2), jnp.uint32))
    n_slot = lambda dtype: arr((NEMOTRON_SLOTS,), dtype)  # noqa: E731
    n_geometry = (ncfg.n_layers, NEMOTRON_POOL, bt)
    n_state = (ncfg.mixer_layers, NEMOTRON_SLOTS, ncfg.ssm_state_size)
    attempt("nemotron_decode",
            lambda: ngen.decode_fn(8).trace(
                *nstate, arr((NEMOTRON_SLOTS, ngen.blocks_per_seq), jnp.int32),
                n_slot(jnp.int32), n_slot(jnp.bool_), n_slot(jnp.bool_),
                n_slot(jnp.float32)), pool=n_geometry, state=n_state,
            weights=shapes_of(nparams), state_kernel="ssd_decode",
            capacity=True)
    attempt(f"nemotron_prefill_{NEMOTRON_BUCKET}",
            lambda: ngen.prefill_fn(NEMOTRON_BUCKET).trace(
                *nstate, arr((ngen.blocks_per_seq,), jnp.int32),
                arr((1, NEMOTRON_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=n_geometry, state=n_state, weights=shapes_of(nparams),
            state_kernel="ssd_decode")

    # LFM2's serve programs whole, at the cell's own sizes: the attention
    # kernel called by the two attention layers alone on a pool of its own
    # depth (32 query heads of 64 over a row of 8 KV heads), the convolution
    # layers' tails the only slot state and written where they lie, the
    # experts' capacity form WALKED in the decode program too (512 pairs > 8
    # x 32 experts); no weight re-laid on a call, and the bytes the chip must
    # hold (3.20B bf16 parameters, a 1.08 GB pool, 8.4 MB of tails).
    from ray_tpu.models import lfm2

    fcfg = lfm2.lfm2_8b_a1b_stage()
    fparams = jax.tree.map(
        lambda x: arr(x.shape, x.dtype),
        jax.eval_shape(lambda key: lfm2.init_params(fcfg, key),
                       jax.random.key(0)))
    fgen = PagedGenerator(fparams, fcfg, slots=LFM2_SLOTS,
                          num_blocks=LFM2_POOL, block_tokens=bt,
                          attention_kernel="pallas")
    fkv = arr((fcfg.n_layers, LFM2_POOL, bt, fcfg.n_kv_heads * fcfg.head_dim))
    fslot = tuple(arr(x.shape, x.dtype) for x in jax.eval_shape(
        lambda: lfm2.init_slot_state(fcfg, LFM2_SLOTS)))
    fstate = (fparams, (fkv, fkv), fslot,
              arr((LFM2_SLOTS, fgen.logits_dim), jnp.float32),
              arr((LFM2_SLOTS, 2), jnp.uint32))
    f_slot = lambda dtype: arr((LFM2_SLOTS,), dtype)  # noqa: E731
    f_geometry = (fcfg.n_layers, LFM2_POOL, bt)
    attempt("lfm2_decode",
            lambda: fgen.decode_fn(8).trace(
                *fstate, arr((LFM2_SLOTS, fgen.blocks_per_seq), jnp.int32),
                f_slot(jnp.int32), f_slot(jnp.bool_), f_slot(jnp.bool_),
                f_slot(jnp.float32)), pool=f_geometry,
            weights=shapes_of(fparams), lfm2_fusions=True)
    attempt(f"lfm2_prefill_{LFM2_BUCKET}",
            lambda: fgen.prefill_fn(LFM2_BUCKET).trace(
                *fstate, arr((fgen.blocks_per_seq,), jnp.int32),
                arr((1, LFM2_BUCKET), jnp.int32), i32, i32, i32, i32),
            pool=f_geometry, weights=shapes_of(fparams))

    rules = ShardingRules()
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    for name, spec in [("train_step_data4", MeshSpec(data=4)),
                       ("train_step_data2_tensor2", MeshSpec(data=2, tensor=2))]:
        mesh = make_mesh(spec, devices=devices)
        bundle = make_train_step(
            loss_fn=lambda p, b, m=mesh: transformer.lm_loss(
                p, b, cfg, mesh=m, rules=rules),
            init_params_fn=lambda key: transformer.init_params(cfg, key),
            logical_params=transformer.logical_axes(cfg),
            mesh=mesh, rules=rules, optimizer=optimizer,
            batch_logical=("batch", None))
        p_shape = jax.eval_shape(
            lambda key: transformer.init_params(cfg, key), jax.random.key(0))
        o_shape = jax.eval_shape(optimizer.init, p_shape)
        placed = lambda tree, sh: jax.tree.map(  # noqa: E731
            lambda x, s: arr(x.shape, x.dtype, s), tree, sh)
        attempt(name, lambda b=bundle, p=p_shape, o=o_shape: b.step.trace(
            placed(p, b.param_shardings), placed(o, b.opt_shardings),
            {"tokens": arr((16, ctx), jnp.int32, b.batch_sharding)}))
    return {"programs": programs, "kernels": kernels, "grids": grids,
            "prefetched": prefetched,
            "scoped_vmem": scoped_vmem, "pool_movers": pool_movers,
            "temp_bytes": temp_bytes, "need_bytes": need_bytes,
            "state_movers": state_movers, "weight_movers": weight_movers,
            "state_roundings": state_roundings,
            "shared_expert_ops": shared_expert_ops,
            "capacity_ops": capacity_ops, "dsa_ops": dsa_ops,
            "lfm2_ops": lfm2_ops, "multisets": multisets,
            "latent_calls": latent_calls, "latent_vmem": latent_vmem,
            "flash_products": flash_products, "flash_movers": flash_movers,
            "pair_rows": pair_rows, "pool_writes": pool_writers,
            "pool_aliases": pool_aliases, "window_operands": window_operands}


@pytest.fixture(scope="module")
def verdict():
    """The children compile everything between them; every test reads the
    one verdict their shares add up to."""
    # Each program is compiled once: the run's compile cache would only be
    # written, never read. And the compiler's flags are its defaults, not
    # the test tree's (conftest's are for CPU programs).
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0",
               ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    env.pop("XLA_FLAGS", None)

    def compile_share(share):
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), str(share),
             str(COMPILE_CHILDREN)], env=env, capture_output=True, text=True,
            timeout=700)

    with concurrent.futures.ThreadPoolExecutor(COMPILE_CHILDREN) as waiters:
        children = list(waiters.map(compile_share, range(COMPILE_CHILDREN)))
    out = {}
    for child in children:
        assert child.returncode == 0, child.stderr[-3000:]
        part = json.loads(child.stdout.strip().splitlines()[-1])
        if "skip" in part:
            pytest.skip(part["skip"])
        for key, by_program in part.items():
            out.setdefault(key, {}).update(by_program)
    return out


def test_kernels_and_sharded_train_step_compile_for_v5e(verdict):
    programs = verdict["programs"]
    assert {"flash_fwd_bwd", "paged_decode", "paged_prefill_1024",
            "train_step_data4", "train_step_data2_tensor2"} <= set(programs)
    refused = {n: v for n, v in programs.items() if v != "ok"}
    assert not refused, json.dumps(refused, indent=1)


def test_kernels_carry_stable_names_and_unchanged_shapes(verdict):
    """Each Pallas call is named for what it is, whatever jit, scan, vjp or
    remat scope calls it, and its custom-call still has the output shape the
    benchmark's metric files match (``:custom-call:bf16[S,H,1,D]`` for the
    decode kernel, four dimensions for prefill, three for flash)."""
    kernels = verdict["kernels"]
    [[name, shape]] = kernels["paged_decode"]
    assert name == "paged_decode_attn"
    assert re.fullmatch(r"bf16\[\d+,\d+,1,\d+\]", shape), shape
    for program in ("paged_verify", "paged_prefill_16", "paged_prefill_1024"):
        [[name, shape]] = kernels[program]
        assert name == "paged_prefill_attn"
        assert re.fullmatch(r"bf16\[\d+,\d+,\d+,\d+\]", shape), shape
    for program in ("flash_fwd_bwd", "train_step_data4",
                    "train_step_data2_tensor2"):
        found = kernels[program]
        # Two kernels since PR 37: the backward is one (``flash_bwd``, first
        # output dk), where it was ``flash_bwd_dq`` and ``flash_bwd_dkv``.
        for kernel in ("flash_fwd", "flash_bwd"):
            hits = [shape for name, shape in found if kernel in name]
            assert hits, (program, kernel, found)
            assert all(re.fullmatch(r"bf16\[\d+,\d+,\d+\]", s)
                       for s in hits), (program, hits)
        assert all("flash_" in name for name, _shape in found), found


def test_flash_kernels_multiply_in_the_input_type(verdict):
    """Traced with bfloat16 inputs, no product inside a flash kernel takes a
    float32 operand (q, k, v and dO as they arrive; ``p`` and ``ds`` rounded
    to that type), and every one sums in float32: two products in the
    forward, five in the one backward kernel (a kernel's loop body and its
    straight-line diagonal chunks each carry the set)."""
    # One step a head (batch, heads: a block of the ``H*D`` axis) while its
    # K and V fit VMEM; then (q blocks, spans) and (spans, q blocks) past that.
    assert verdict["grids"]["flash_fwd_bwd"] == [[2, 12, 1, 1]] * 2
    assert verdict["grids"]["flash_fwd_bwd_long"] == [[1, 2, 4, 2],
                                                      [1, 2, 2, 4]]
    products = verdict["flash_products"]["flash_fwd_bwd"]
    assert sorted(products) == ["flash_bwd", "flash_fwd"], products
    for kernel, per_tile in (("flash_fwd", 2), ("flash_bwd", 5)):
        dots = products[kernel]
        assert dots and len(dots) % per_tile == 0, (kernel, len(dots))
        assert all(dot == ["bfloat16", "bfloat16", "float32"]
                   for dot in dots), (kernel, dots)


@pytest.mark.parametrize("program", ["flash_fwd_bwd", "train_step_data4",
                                     "train_step_data2_tensor2"])
def test_flash_kernels_take_the_array_where_the_projections_leave_it(
        verdict, program):
    """No ``copy``, ``transpose`` or layout fusion between the projections
    and the flash kernels, either way, and no ``bf16[B,H,L,D]`` left: the
    kernels read and write ``[B, H*D, L]``, which is how XLA lays what the
    ``bld,dhk->blhk`` products write (PR 41; ``_fold`` / ``_unfold`` paid
    ten such copies a layer)."""
    assert verdict["flash_movers"][program] == []


def test_flash_layout_scan_sees_what_fold_and_unfold_did():
    """The scan itself, on what the parent's compiled train step held
    (compile-only, PR 41), on a kernel that asks for ``[B, L, H*D]``
    row-major, and on the program as it is now."""
    kernel = 'custom_call_target="tpu_custom_call"'
    folded = f"""
%fused_computation.5 (p0: bf16[4,1024,768], p1: bf16[768,12,64]) -> bf16[4,12,1024,64] {{
  %p0 = bf16[4,1024,768]{{1,2,0}} parameter(0)
  %p1 = bf16[768,12,64]{{0,2,1}} parameter(1)
  ROOT %convolution.1 = bf16[4,12,1024,64]{{2,3,1,0}} convolution(%p0, %p1), dim_labels=0bf_io0->0bf
}}
ENTRY %main (x: bf16[4,1024,768], w: bf16[768,12,64]) -> bf16[4,12,1024,64] {{
  %x = bf16[4,1024,768]{{1,2,0}} parameter(0)
  %w = bf16[768,12,64]{{0,2,1}} parameter(1)
  %fusion.516 = bf16[4,12,1024,64]{{2,3,1,0:T(8,128)(2,1)}} fusion(%x, %w), kind=kOutput, calls=%fused_computation.5
  %copy.289 = bf16[4,12,1024,64]{{3,2,1,0:T(8,128)(2,1)}} copy(%fusion.516)
  %bitcast.1 = bf16[48,1024,64]{{2,1,0:T(8,128)(2,1)}} bitcast(%copy.289)
  %flash_fwd.3 = (bf16[48,1024,64]{{2,1,0}}, f32[48,1,1,1024]{{3,2,1,0}}) custom-call(%bitcast.1, %bitcast.1, %bitcast.1), {kernel}
  %get-tuple-element.7 = bf16[48,1024,64]{{2,1,0}} get-tuple-element(%flash_fwd.3), index=0
  %bitcast.2 = bf16[4,12,1024,64]{{3,2,1,0}} bitcast(%get-tuple-element.7)
  ROOT %copy.292 = bf16[4,12,1024,64]{{2,3,1,0}} copy(%bitcast.2)
}}
"""
    assert [(op, name) for op, name, _s in flash_layout_movers(
        folded, 12, 1024, 64)] == [
        ("copy", "copy.289"), ("copy", "copy.292"), ("fusion", "fusion.516")]
    row_major = f"""
%fused_computation.9 (p0: bf16[4,1024,12,64]) -> bf16[4,1024,768] {{
  %p0 = bf16[4,1024,12,64]{{1,3,2,0}} parameter(0)
  %bitcast.8 = bf16[4,1024,768]{{1,2,0}} bitcast(%p0)
  ROOT %copy.3 = bf16[4,1024,768]{{2,1,0}} copy(%bitcast.8)
}}
ENTRY %main (q: bf16[4,1024,12,64]) -> bf16[4,1024,768] {{
  %q = bf16[4,1024,12,64]{{1,3,2,0}} parameter(0)
  %bitcast.537 = bf16[4,1024,768]{{1,2,0}} bitcast(%q)
  %copy.284 = bf16[4,1024,768]{{2,1,0}} copy(%bitcast.537)
  %copy_fusion.2 = bf16[4,1024,768]{{2,1,0}} fusion(%q), kind=kLoop, calls=%fused_computation.9
  %flash_fwd.16 = (bf16[4,1024,768]{{2,1,0}}, f32[4,12,1,1024]{{3,2,1,0}}) custom-call(%copy.284, %copy_fusion.2, %copy.284), {kernel}
  %pallas_call.59 = bf16[4,1024,768]{{2,1,0}} get-tuple-element(%flash_fwd.16), index=0
  %copy.287 = bf16[4,1024,768]{{1,2,0}} copy(%pallas_call.59)
  %copy.9 = bf16[4,1024,768]{{1,2,0}} copy(%bitcast.537)
  ROOT %add.1 = bf16[4,1024,768]{{1,2,0}} add(%copy.287, %copy.9)
}}
"""
    assert [(op, name) for op, name, _s in flash_layout_movers(
        row_major, 12, 1024, 64)] == [
        ("copy", "copy.284"), ("copy", "copy.287"),
        ("fusion", "copy_fusion.2")]        # not copy.9: no kernel's
    in_place = f"""
ENTRY %main (q: bf16[4,1024,12,64]) -> bf16[4,1024,12,64] {{
  %q = bf16[4,1024,12,64]{{1,3,2,0}} parameter(0)
  %bitcast.5 = bf16[4,768,1024]{{2,1,0}} bitcast(%q)
  %flash_fwd.16 = (bf16[4,768,1024]{{2,1,0}}, f32[4,12,1,1024]{{3,2,1,0}}) custom-call(%bitcast.5, %bitcast.5, %bitcast.5), {kernel}
  %pallas_call.59 = bf16[4,768,1024]{{2,1,0}} get-tuple-element(%flash_fwd.16), index=0
  ROOT %bitcast.6 = bf16[4,1024,12,64]{{1,3,2,0}} bitcast(%pallas_call.59)
}}
"""
    assert flash_layout_movers(in_place, 12, 1024, 64) == []


@pytest.mark.parametrize("num_blocks", SERVE_POOLS)
@pytest.mark.parametrize("width", sorted(SERVE_WIDTHS))
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serve_programs_move_no_pool_sized_data(verdict, program, width,
                                                num_blocks):
    """``paged_decode`` and ``paged_prefill`` hold the KV pool in one layout
    from residency through write to read: besides the in-place scatters, the
    optimized module has no ``copy``, ``slice`` or fusion whose output is the
    pool or one layer of it (PR 25: they were 40-59% of the serve cells'
    device time), and its temporaries do not grow with the pool."""
    name = f"serve_{program}_{width}_{num_blocks}"
    assert verdict["programs"][name] == "ok", verdict["programs"][name]
    assert verdict["pool_movers"][name] == []
    temps = [verdict["temp_bytes"][f"serve_{program}_{width}_{n}"]
             for n in SERVE_POOLS]
    assert max(temps) - min(temps) < 1 << 20, temps
    [[kernel, shape]] = verdict["kernels"][name]
    assert kernel == ("paged_decode_attn" if program == "decode"
                      else "paged_prefill_attn")
    assert re.fullmatch(r"bf16\[\d+,\d+,\d+,64\]", shape), shape


@pytest.mark.parametrize("num_blocks", SERVE_POOLS)
def test_gpt2_decode_writes_its_rows_inside_the_kernel(verdict, num_blocks):
    """``paged_decode`` over a pool row of whole lanes hands each step's new
    K and V row to the kernel (``paged_attention_append``): the compiled
    program holds no ``scatter`` and no ``dynamic-update-slice`` into the
    pool (they were 48 fusions a token step, the first operation of a chat
    token: PERF.md, PR 48), no ``copy`` of the pool's shape in their place
    at any of the three pool sizes, and every kernel call gives back both
    pools in the buffers they came in. gpt2-xl's 1,600 lanes take the form
    with the groups on the grid, which writes nothing: its two scatters a
    layer stay. A prefill writes a bucket of rows and scatters them."""
    name = f"serve_decode_medium_{num_blocks}"
    assert verdict["programs"][name] == "ok", verdict["programs"][name]
    assert verdict["pool_writes"][name] == []
    assert verdict["pool_movers"][name] == []
    assert verdict["pool_aliases"][name] == [[[1, 4], [2, 5]]] * SERVE_LAYERS
    xl = verdict["pool_writes"][f"serve_decode_xl_{num_blocks}"]
    assert [op for op, _name in xl] == ["scatter"] * 2 * SERVE_LAYERS, xl
    assert verdict["pool_aliases"][f"serve_decode_xl_{num_blocks}"] == [
        []] * SERVE_LAYERS
    prefill = verdict["pool_writes"][f"serve_prefill_medium_{num_blocks}"]
    assert [op for op, _name in prefill] == ["scatter"] * 2 * SERVE_LAYERS


@pytest.mark.parametrize("program,layers,multiset", [
    ("olmo_decode", 4, OLMO_DECODE), ("falcon_decode", 6, FALCON_DECODE),
    ("nemotron_decode", 2, None), ("trinity_decode", None, TRINITY_DECODE)])
def test_the_other_families_decode_programs_are_what_they_were(
        verdict, program, layers, multiset):
    """They call the kernel WITHOUT a row, each after the write of its own
    forward: two scatters an attention layer (Trinity's one full layer's
    write is fused into another shape and not counted), no aliased kernel
    output, and the instructions the compiled program had on PR 47's tree
    (Nemotron-H's pair is held by
    ``test_nemotron_decode_is_the_program_it_was``). A PR that changes one
    of these programs on purpose takes a new pair."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    writes = verdict["pool_writes"][program]
    if layers is not None:
        assert [op for op, _name in writes] == ["scatter"] * 2 * layers, writes
    calls = verdict["pool_aliases"][program]
    assert calls and not any(calls), calls
    if multiset is not None:
        assert verdict["multisets"][program] == multiset


# instruction_multiset() of the other compiled decode programs on PR 53's
# tree (b3bda2d), taken the same way: PR 54 gave every family's PREFILL one
# operand more for its attention kernels (the count of real rows) and meant to
# leave every decode program, which passes none, the program it was.
DECODE_PROGRAMS = {
    "serve_decode_medium_1281": [1644, "966b7115aef33b1a"],
    "serve_decode_medium_1537": [1644, "6a767aef989299b6"],
    "serve_decode_medium_2049": [1644, "5051d29f15557ee9"],
    "serve_decode_xl_1281": [1816, "dfdbbaf5a8386844"],
    "serve_decode_xl_1537": [1816, "958acc2688ad5774"],
    "serve_decode_xl_2049": [1816, "18d2878595530d39"],
    "longcat_decode": [7335, "409f8a2cd98666fd"],
    "kimi_decode": [7594, "de36f56732c0b40e"],
    "mimo_decode": [6626, "1d24984d144679e6"],
    "glm_decode": [7250, "065c359f4e3468bd"],
    "paged_decode": [30, "72079d8e70b19d47"],
    "paged_verify": [29, "64f8a62669735346"],
}


@pytest.mark.parametrize("program", sorted(DECODE_PROGRAMS))
def test_a_decode_program_is_the_one_it_was_before_prefills_were_counted(
        verdict, program):
    """A decode step and a verify pass no count of real rows (every row is
    real): their attention kernels take the three prefetched operands they
    took, and the compiled program's instructions are PR 53's. A PR that
    changes one of these programs on purpose takes a new pair."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    assert verdict["multisets"][program] == DECODE_PROGRAMS[program]
    counts = {kernel: n for kernel, n in verdict["prefetched"][program].items()
              if kernel.endswith("_attn")}
    assert counts and all(n == [3] for n in counts.values()), counts


@pytest.mark.parametrize("program,kernels", [
    ("serve_prefill_medium_1281", ["paged_prefill_attn"]),
    ("serve_prefill_xl_1281", ["paged_prefill_attn"]),      # groups on the grid
    ("longcat_prefill_1024", ["mla_prefill_attn"]),
    (f"kimi_prefill_{KIMI_BUCKETS[-1]}", ["mla_prefill_attn"]),
    (f"glm_prefill_{GLM_BUCKET}", ["mla_prefill_attn"]),    # with keep bits
    (f"olmo_prefill_{OLMO_BUCKET}", ["paged_prefill_attn"]),
    (f"falcon_prefill_{FALCON_BUCKET}", ["paged_prefill_attn"]),
    (f"nemotron_prefill_{NEMOTRON_BUCKET}", ["paged_prefill_attn"]),
    (f"trinity_prefill_{TRINITY_BUCKET}",
     ["paged_prefill_attn", "window_prefill_attn"]),        # the ring's walk too
    (f"mimo_prefill_{MIMO_BUCKET}",
     ["paged_prefill_attn", "window_prefill_attn"]),        # sinks, a narrow V
])
def test_a_prefill_hands_its_attention_kernels_the_count_of_real_rows(
        verdict, program, kernels):
    """Every family's prefill program compiles for a v5e with the count as a
    FOURTH scalar-prefetch operand of each of its attention kernels
    (``paged_attention(queries=)``), under the names they had."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    counts = {kernel: n for kernel, n in verdict["prefetched"][program].items()
              if kernel.endswith("_attn")}
    assert counts == {kernel: [4] for kernel in kernels}, counts
    # the kernel alone, as a caller without a count traces it: three
    assert verdict["prefetched"]["paged_prefill_1024"] == {
        "paged_prefill_attn": [3]}


def test_the_pool_write_scan_sees_a_scatter_and_an_aliased_kernel():
    """The two scans themselves, on the parent's decode step and on this
    tree's."""
    kernel = 'custom_call_target="tpu_custom_call"'
    parent = f"""
%fused_computation.3 (p0: bf16[2,1281,16,1024]) -> bf16[2,1281,16,1024] {{
  %p0 = bf16[2,1281,16,1024]{{3,2,1,0}} parameter(0)
  ROOT %scatter.57 = bf16[2,1281,16,1024]{{3,2,1,0:T(8,128)(2,1)}} scatter(%p0, %i, %u)
}}
ENTRY %main (k: bf16[2,1281,16,1024]) -> bf16[36,16,1,64] {{
  %k = bf16[2,1281,16,1024]{{3,2,1,0}} parameter(0)
  %steps = s32[8,36]{{1,0}} dynamic-update-slice(%t, %u, %i)
  %fusion.301 = bf16[2,1281,16,1024]{{3,2,1,0}} fusion(%k), kind=kCustom, calls=%fused_computation.3
  ROOT %paged_decode_attn.10 = bf16[36,16,1,64]{{3,2,1,0}} custom-call(%t, %fusion.301), {kernel}
}}
"""
    assert pool_writes(parent, 2, 1281, 16) == [["scatter", "scatter.57"]]
    assert kernel_aliases(parent, "paged_decode_attn") == [[]]
    change = f"""
ENTRY %main (k: bf16[2,1281,16,1024]) -> bf16[36,16,1,64] {{
  %paged_decode_attn.10 = (bf16[36,16,1,64]{{3,2,1,0}}, bf16[2,1281,16,1024]{{3,2,1,0}}, bf16[2,1281,16,1024]{{3,2,1,0}}) custom-call(%t, %k, %v), {kernel}, output_to_operand_aliasing={{{{1}}: (4, {{}}), {{2}}: (5, {{}})}}, metadata={{}}
}}
"""
    assert pool_writes(change, 2, 1281, 16) == []
    assert kernel_aliases(change, "paged_decode_attn") == [[[1, 4], [2, 5]]]


@pytest.mark.parametrize("num_blocks", SERVE_POOLS)
@pytest.mark.parametrize("width", sorted(SERVE_WIDTHS))
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_gpt2_serve_programs_read_their_weights_where_they_lie(
        verdict, program, width, num_blocks):
    """``paged_decode`` and ``paged_prefill`` are called with the working
    tree ``generate.gpt2_working_params`` made when the generator was built:
    every leaf in the compute type, one array a matrix a layer, the head's
    matrix as its product reads it, a table's rows in whole lanes. Handed
    the float32 stacked tree, each call began by converting 355M parameters,
    copying each layer's matrix out of the converted slab and transposing
    the tied table: 13-16% of the GPT-2 serve cells' device time (PERF.md,
    PR 32). Neither ``ENTRY`` nor a loop's body writes a weight-sized array
    now."""
    name = f"serve_{program}_{width}_{num_blocks}"
    assert verdict["programs"][name] == "ok", verdict["programs"][name]
    assert verdict["weight_movers"][name] == []


@pytest.mark.parametrize("program,found", [
    ("longcat_decode", [["copy", "bf16[512,64,128]", "ENTRY"]] * 16),
    ("longcat_prefill_1024", []),
    ("olmo_decode", []), ("olmo_prefill_2048", [])])
def test_what_the_weight_scan_finds_in_the_other_families(verdict, program,
                                                          found):
    """The families that store bfloat16, one array a matrix, and give no
    ``working_params``: what the same scan finds in their programs TODAY.
    LongCat's decode re-lays the two absorbed projections of each of its
    eight latent sublayers on every call (16 x 8.4 MB written: ~0.3 ms of a
    230 ms call; a working tree of its own would hold them as the kernel's
    products read them). Olmo-Hybrid's programs and LongCat's prefill move
    none: their trace's ``slice-done bf16[960,11520]`` and ``copy-done
    f32[3840]`` are the compiler's prefetches into VMEM."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    assert [[op, shape.split("{")[0], where] for op, _name, shape, where
            in verdict["weight_movers"][program]] == found


@pytest.mark.parametrize("num_blocks", SERVE_POOLS)
@pytest.mark.parametrize("width", sorted(SERVE_WIDTHS))
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_kernel_walks_the_table_itself(verdict, kind, width, num_blocks):
    """The table is no grid axis: a call is ``slots x query tiles`` grid
    steps (36, and 8 for the 1024 bucket) and the walk over a slot's live
    blocks is a loop inside each, whose K and V buffers fit the chip's
    scoped VMEM; name and output shape are what the benchmark's metrics
    match, and the pool goes in where it lies. gpt2-xl's 1,600 lanes are no
    multiple of 128, and Mosaic refuses to slice such a pool for a DMA:
    there the groups of eight entries stay a third grid axis (64 / 8)."""
    name = f"kernel_{kind}_{width}_{num_blocks}"
    assert verdict["programs"][name] == "ok", verdict["programs"][name]
    steps = [SERVE_SLOTS, 1] if kind == "decode" else [1, 8]
    if width == "xl":
        steps.append(8)
    assert verdict["grids"][name] == [steps]
    [vmem] = verdict["scoped_vmem"][name]
    assert 0 < vmem < V5E_SCOPED_VMEM, vmem
    [[kernel, shape]] = verdict["kernels"][name]
    assert kernel == f"paged_{kind}_attn"
    tokens = "1" if kind == "decode" else r"\d+"
    assert re.fullmatch(rf"bf16\[\d+,\d+,{tokens},64\]", shape), shape
    if (kind, width) == ("decode", "medium"):
        # The one call the chat cells' kernel metrics read, a parked slot's
        # step skipped inside it: the same name over the same 36 slots.
        assert shape == f"bf16[{SERVE_SLOTS},16,1,64]"
    assert verdict["pool_movers"][name] == []
    # The serve programs whole call it once a layer (their prefill is the
    # 256 bucket: two tiles).
    steps[1] = 1 if kind == "decode" else 2
    serve = f"serve_{kind}_{width}_{num_blocks}"
    assert verdict["grids"][serve] == [steps] * SERVE_LAYERS


@pytest.mark.parametrize("program,kernel,shape", [
    ("longcat_decode", "mla_decode_attn", "bf16[128,1,64,512]"),
    ("longcat_prefill_1024", "mla_prefill_attn", "bf16[1,64,1024,512]")])
def test_longcat_serve_programs_fit_the_chip(verdict, program, kernel, shape):
    """LongCat-Flash's ``paged_decode`` and its largest ``paged_prefill`` at
    the sizes of ``longcat-flash-omni.moe-decode``: they compile for a v5e,
    arguments plus temporaries stay under the chip's 16 GB, the latent
    kernels carry their names and output shapes (the benchmark's
    ``mla_attn_*`` metrics match ``^mla_decode_attn:``), the expert layer is
    the TPU's grouped product (``ragged-dot``) and no instruction copies or
    slices pool-shaped data."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    assert verdict["need_bytes"][program] < 16e9, verdict["need_bytes"]
    # weights 10.35 GB + pool 1.20 GB: the cell fills the chip as reckoned
    assert verdict["need_bytes"][program] > 11.5e9, verdict["need_bytes"]
    found = verdict["kernels"][program]
    assert [s for n, s in found if n == kernel] == [shape], found
    assert any(n.startswith("ragged-dot") for n, _s in found), found
    assert {n for n, _s in found} <= {kernel, "ragged-dot-none",
                                      "ragged-dot-metadata"}, found
    assert verdict["pool_movers"][program] == []


# The benchmark's decode-attention metric finds its kernel by this pattern
# (benchmark/metrics/paged_attn_roofline.json, a file no PR but a benchmark
# PR may edit): the kernel's name is not in it, its output shape is.
PAGED_ATTN_PATTERN = r":custom-call:bf16\[\d+,\d+,1,\d+\]"


@pytest.mark.parametrize("program,kernels", [
    ("olmo_decode", {"gdn_decode": "f32[12,48,96,5760]",
                     "paged_decode_attn": "bf16[48,30,1,128]"}),
    ("olmo_prefill_2048", {"paged_prefill_attn": "bf16[1,30,2048,128]"})])
def test_olmo_hybrid_serve_programs_fit_the_chip(verdict, program, kernels):
    """Olmo-Hybrid's ``paged_decode`` and its largest ``paged_prefill`` at
    the sizes of ``olmo-hybrid-7b.hybrid-decode``: they compile for a v5e,
    arguments plus temporaries stay under 14 GB (the check's float32 pass
    runs beside them), the kernels carry their names and output shapes, no
    instruction copies or slices data the size of the pool or of the slot
    state, and of the decode program's Pallas calls the benchmark's
    ``paged_attn_roofline`` pattern matches the attention kernel ALONE: the
    state kernel's outputs have another shape, or its time would be counted
    as attention's."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 8.20 GB + pool 3.21 GB + slot state 1.31 GB
    assert 12.7e9 < verdict["need_bytes"][program] < 14e9, verdict["need_bytes"]
    found = verdict["kernels"][program]
    assert dict(found) == kernels, found
    assert verdict["pool_movers"][program] == []
    assert verdict["state_movers"][program] == []
    assert all(0 < v < V5E_SCOPED_VMEM
               for v in verdict["scoped_vmem"][program])
    matched = [name for name, shape in found
               if re.search(PAGED_ATTN_PATTERN, f"{name}:custom-call:{shape}")]
    assert matched == (["paged_decode_attn"] if program == "olmo_decode"
                       else []), found


@pytest.mark.parametrize("program,kernels", [
    ("falcon_decode", {"ssd_decode": "f32[6,64,256,4096]",
                       "paged_decode_attn": "bf16[64,20,1,128]"}),
    ("falcon_prefill_1024", {"paged_prefill_attn": "bf16[1,20,1024,128]"})])
def test_falcon_h1_serve_programs_fit_the_chip(verdict, program, kernels):
    """Falcon-H1's ``paged_decode`` and its largest ``paged_prefill`` at the
    sizes of ``falcon-h1-34b.ssm-decode``: they compile for a v5e, arguments
    plus temporaries stay under 15 GB (the check's float32 pass, 1.07 GB of
    logits, runs beside them). The state kernel's operand is float32 and
    holds 6 x 64 x 1,048,576 elements, in whatever folding the kernel takes:
    a state kept in bfloat16 between steps reads inside the cell's limit on
    the chip (PERF.md, PR 40), so THIS is what holds its precision. The
    grouped-query kernel runs under the attention kernel's names, with all
    twenty query heads in its output, on a pool row of the four KV heads. No
    instruction copies or slices data the size of the pool, of the slot
    state or of a weight; of the decode program's Pallas calls the
    benchmark's ``paged_attn_roofline`` pattern matches the attention kernel
    ALONE and ``ssd_state_roofline``'s the state kernel alone; B and C reach
    the state kernel as three bfloat16 parts each: six roundings a call, or
    more where XLA recomputes an earlier part inside a later part's fusion
    (it does: twelve), never fewer, which is what a dropped cut reads."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 10.51 GB + slot state 1.62 GB + pool 0.77 GB + last 0.07 GB
    assert 12.9e9 < verdict["need_bytes"][program] < 15e9, verdict["need_bytes"]
    found = verdict["kernels"][program]
    assert dict(found) == kernels, found
    assert verdict["pool_movers"][program] == []
    assert verdict["state_movers"][program] == []
    assert verdict["weight_movers"][program] == []
    assert all(0 < v < V5E_SCOPED_VMEM
               for v in verdict["scoped_vmem"][program])
    with open(os.path.join(REPO, "benchmark", "metrics",
                           "ssd_state_roofline.json")) as f:
        ssd_pattern = json.load(f)["pattern"]
    names = [f"{name}:custom-call:{shape}" for name, shape in found]
    decode = program == "falcon_decode"
    assert [n.split(":")[0] for n in names
            if re.search(PAGED_ATTN_PATTERN, n)] == (
        ["paged_decode_attn"] if decode else [])
    assert [n.split(":")[0] for n in names if re.search(ssd_pattern, n)] == (
        ["ssd_decode"] if decode else [])
    if decode:
        shape = [int(n) for n in re.findall(r"\d+", kernels["ssd_decode"])[1:]]
        assert kernels["ssd_decode"].startswith("f32[")
        assert math.prod(shape) == 6 * 64 * 1_048_576 and shape[:2] == [6, 64]
        calls, roundings = verdict["state_roundings"][program]
        assert calls == 6 and roundings >= 6 * calls, (calls, roundings)


@pytest.mark.parametrize("program,kernels,need", [
    ("trinity_decode", {"window_decode_attn": "bf16[64,48,1,128]",
                        "paged_decode_attn": "bf16[64,48,1,128]"},
     (11.4e9, 11.8e9)),
    ("trinity_prefill_8192", {"window_prefill_attn": "bf16[1,48,8192,128]",
                              "paged_prefill_attn": "bf16[1,48,8192,128]"},
     (12.3e9, 12.7e9))])
def test_trinity_serve_programs_fit_the_chip(verdict, program, kernels, need):
    """Trinity's ``paged_decode`` and its largest ``paged_prefill`` at the
    sizes of ``trinity-large-preview.window-decode``: they compile for a v5e
    (48 query heads of 128: a prefill tile of 64 queries keeps the kernel's
    accumulators inside the 16 MB of scoped VMEM), arguments plus
    temporaries stay under 14 GB (the check's float32 pass at 8,192 tokens
    runs beside the 11.5 GB resident). The window layers' kernel runs under a
    name of its OWN, so that a trace tells the two kinds of attention apart;
    both have all 48 query heads in their output over a pool row of the 8 KV
    heads. No instruction copies or slices data the size of the pool, of the
    rings (viewed as blocks by a reshape that must stay free) or, in the
    decode program, of a weight."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 5.02 GB + rings 4.43 GB + pool 2.02 GB
    assert need[0] < verdict["need_bytes"][program] < need[1], verdict["need_bytes"]
    found = {n: s for n, s in verdict["kernels"][program]
             if not n.startswith("ragged-dot")}
    assert found == kernels, verdict["kernels"][program]
    assert verdict["pool_movers"][program] == []
    assert verdict["state_movers"][program] == []
    if program == "trinity_decode":
        assert verdict["weight_movers"][program] == []
    # the grouped product's metadata call reports none; the attention
    # kernels' 1.3 MB (decode) and 10.0 MB (a prefill tile of 64 queries)
    vmem = verdict["scoped_vmem"][program]
    assert max(vmem) > 1 << 20 and all(0 <= v < V5E_SCOPED_VMEM for v in vmem)
    # one window call a sliding layer, one paged call for the full layer
    assert sorted(verdict["grids"][program]) == sorted(
        [[64, 1]] * 5 if program == "trinity_decode" else [[1, 128]] * 5)


@pytest.mark.parametrize("program,kernels,need", [
    ("mimo_decode", {"window_decode_attn": "bf16[128,64,1,128]",
                     "paged_decode_attn": "bf16[128,64,1,128]"},
     (7.7e9, 8.1e9)),
    ("mimo_prefill_4096", {"window_prefill_attn": "bf16[1,64,4096,128]",
                           "paged_prefill_attn": "bf16[1,64,4096,128]"},
     (8.6e9, 9.1e9))])
def test_mimo_serve_programs_fit_the_chip(verdict, program, kernels, need):
    """MiMo-V2-Flash's ``paged_decode`` and its largest ``paged_prefill`` at
    the sizes of ``mimo-v2-flash.swa-decode`` (128 slots, a pool of 32,769
    blocks; at ISSUE 49's first sizes, 256 slots and 65,537 blocks, the two
    need 11.26 and 12.14 GB): they compile for a v5e (64 query heads of 192 in chunks of two:
    a prefill tile of 32 queries keeps the q block inside the scoped VMEM)
    and arguments plus temporaries stay under ISSUE 49's 14.5 GB. Both
    kernels' outputs are the V side's 128 wide for all 64 query heads; the
    window layers' decode kernel takes q rows of 8 x 192 lanes, rings of 8 x
    192 and 8 x 128 lanes viewed as blocks, and the sinks, one a query head,
    float32. No instruction copies or slices data the size of the pool or of
    the rings. Of the weights the decode program moves two, both layer 0's
    and both the COMPILER'S: it parks the first layer's ``w_q`` and ``w_k``
    (the first products' operands) in its alternate memory before the loop,
    turned to the layout it wants for a product whose output is cut into
    heads of 192 (1.5 lane tiles), and brings them back a step: 106 MB a
    step beside the ~8 GB a step reads (PERF.md 7, item 31). No other layer's
    matrix, no expert's, nor the head is copied."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 4.44 GB + pool 2.68 GB + rings 0.63 GB
    assert need[0] < verdict["need_bytes"][program] < need[1], verdict["need_bytes"]
    assert verdict["need_bytes"][program] < 14.5e9
    found = {n: s for n, s in verdict["kernels"][program]
             if not n.startswith("ragged-dot")}
    assert found == kernels, verdict["kernels"][program]
    assert verdict["pool_movers"][program] == []
    assert verdict["state_movers"][program] == []
    vmem = verdict["scoped_vmem"][program]
    assert max(vmem) > 1 << 20 and all(0 <= v < V5E_SCOPED_VMEM for v in vmem)
    if program == "mimo_decode":
        moved = sorted((shape.split("{")[0], where == "ENTRY") for _op, _name,
                       shape, where in verdict["weight_movers"][program])
        assert moved == [("bf16[4096,12288]", False), ("bf16[4096,12288]", True),
                         ("bf16[4096,768]", False), ("bf16[4096,768]", True)]
        blocks = MIMO_SLOTS * 3                  # a ring: 128 + 64 rows
        assert verdict["window_operands"][program] == [[
            f"s32[{MIMO_SLOTS},3]", f"s32[{MIMO_SLOTS}]", "s32[1]",
            f"bf16[{MIMO_SLOTS},1,64,1536]", f"bf16[5,{blocks},64,1536]",
            f"bf16[5,{blocks},64,1024]", "f32[64,1]"]]
    # one window call a window layer, one paged call a full layer
    assert sorted(verdict["grids"][program]) == sorted(
        [[MIMO_SLOTS, 1]] * 7 if program == "mimo_decode" else [[1, 128]] * 7)


@pytest.mark.parametrize("program,kernels,need", [
    ("nemotron_decode", {"ssd_decode": "f32[6,128,128,4096]",
                         "paged_decode_attn": "bf16[128,32,1,128]"},
     (10.2e9, 10.5e9)),
    ("nemotron_prefill_2176", {"paged_prefill_attn": "bf16[1,32,2176,128]"},
     (10.5e9, 10.685e9))])
def test_nemotron_h_serve_programs_fit_the_chip(verdict, program, kernels,
                                                need):
    """Nemotron-H's ``paged_decode`` and its largest ``paged_prefill`` at the
    sizes of ``nemotron-3-nano-30b-a3b.reason-decode``: they compile for a
    v5e and arguments plus temporaries leave room for the check's float32
    pass (0.57 GB of logits) beside them. A layer is ONE thing: the state
    kernel's operand is float32 ``[6 mixer layers, 128 slots, 128, 4096]``
    and it is called six times (a whole group's 512 lanes a block: a grid of
    128 slots x 8), the attention kernel twice on a pool of TWO layers with
    all 32 query heads in its output over a row of the two KV heads, the
    routed experts' capacity form by the five expert layers: the decode
    step's behind a ``cond`` whose other branch is the grouped product, the
    prefill bucket's walked in passes with NO grouped product in the program
    (``ops/moe.py:held_capacity``: 256 rows an expert here), its need at or
    under the 10.68 GB it took with one. No instruction copies or
    slices data the size of the pool, of the slot state or, in the decode
    program, of a weight; of the decode program's Pallas calls the
    benchmark's ``paged_attn_roofline`` pattern matches the attention kernel
    ALONE and ``ssd_state_roofline``'s the state kernel alone; B and C reach
    the state kernel as three bfloat16 parts each."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 8.07 GB as stored (the experts 1,920 wide) + slot state 1.64 GB
    # + pool 0.54 GB + last 0.03 GB; temporaries 0.03 GB (decode), 0.39 GB
    assert need[0] < verdict["need_bytes"][program] < need[1], verdict["need_bytes"]
    grouped = sorted(s for n, s in verdict["kernels"][program]
                     if n == "ragged-dot-none")
    assert grouped == (["f32[768,1920]", "f32[768,2688]"]
                       if program == "nemotron_decode" else []), grouped
    found = [[n, s] for n, s in verdict["kernels"][program]
             if not n.startswith("ragged-dot")]
    assert dict(found) == kernels, verdict["kernels"][program]
    assert verdict["pool_movers"][program] == []
    assert verdict["state_movers"][program] == []
    assert all(0 <= v < V5E_SCOPED_VMEM
               for v in verdict["scoped_vmem"][program])
    with open(os.path.join(REPO, "benchmark", "metrics",
                           "ssd_state_roofline.json")) as f:
        ssd_pattern = json.load(f)["pattern"]
    names = [f"{name}:custom-call:{shape}" for name, shape in found]
    decode = program == "nemotron_decode"
    assert [n.split(":")[0] for n in names
            if re.search(PAGED_ATTN_PATTERN, n)] == (
        ["paged_decode_attn"] if decode else [])
    assert [n.split(":")[0] for n in names if re.search(ssd_pattern, n)] == (
        ["ssd_decode"] if decode else [])
    # the routed experts' first matrix is stored in whole lane tiles (1,920
    # for the published 1,856) and K/V's weight D-minor: stored otherwise
    # each was copied on every token step (3.2 GB of temporaries)
    assert verdict["weight_movers"][program] == []
    assert verdict["temp_bytes"][program] < 0.5e9, verdict["temp_bytes"]
    if decode:
        calls, roundings = verdict["state_roundings"][program]
        assert calls == 6 and roundings >= 6 * calls, (calls, roundings)
        grids = verdict["grids"][program]
        assert grids.count([128, 8]) == 6 and grids.count([128, 1]) == 2, grids


@pytest.mark.parametrize("program,kernels,need", [
    ("lfm2_decode", {"paged_decode_attn": "bf16[128,32,1,64]"},
     (7.4e9, 7.65e9)),
    ("lfm2_prefill_2176", {"paged_prefill_attn": "bf16[1,32,2176,64]"},
     (7.5e9, 7.9e9))])
def test_lfm2_serve_programs_fit_the_chip(verdict, program, kernels, need):
    """LFM2's ``paged_decode`` and its largest ``paged_prefill`` at the sizes
    of ``lfm2-8b-a1b.conv-decode``: they compile for a v5e and arguments plus
    temporaries leave room for the check's float32 pass (0.54 GB of logits)
    beside them. The attention kernel is the ONLY Pallas call, twice on
    a pool of TWO layers with all 32 query heads of 64 in its output over a
    row of the eight KV heads; there is no state kernel and no grouped
    product: the experts' capacity form is walked in passes in BOTH programs
    (64 rows an expert in the decode step, 256 in the bucket). No
    instruction copies or slices data the size of the pool or of a weight
    (``W_q`` is stored a head first for that; the tails are 11.5 MB and a
    layer reads its own megabyte of them: not scanned); of the programs'
    Pallas calls the benchmark's ``paged_attn_roofline`` pattern matches the
    decode kernel ALONE."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 6.39 GB + pool 1.08 GB + tails 0.01 GB + last 0.03 GB;
    # temporaries 0.03 GB (decode), 0.2 GB (the bucket)
    assert need[0] < verdict["need_bytes"][program] < need[1], verdict["need_bytes"]
    assert dict(verdict["kernels"][program]) == kernels, verdict["kernels"][program]
    assert verdict["pool_movers"][program] == []
    assert verdict["weight_movers"][program] == []
    assert all(0 <= v < V5E_SCOPED_VMEM
               for v in verdict["scoped_vmem"][program])
    names = [f"{name}:custom-call:{shape}"
             for name, shape in verdict["kernels"][program]]
    decode = program == "lfm2_decode"
    assert [n.split(":")[0] for n in names
            if re.search(PAGED_ATTN_PATTERN, n)] == (
        ["paged_decode_attn"] if decode else [])
    assert verdict["temp_bytes"][program] < 0.5e9, verdict["temp_bytes"]
    if decode:
        assert verdict["grids"][program].count([128, 1]) == 2, verdict["grids"]
        assert verdict["prefetched"][program] == {"paged_decode_attn": [3]}
    else:
        assert verdict["prefetched"][program] == {"paged_prefill_attn": [4]}


def test_lfm2s_patterns_match_their_fusions_alone(verdict):
    """``lfm2_expert_ffn_*`` and ``short_conv_*`` find the family's fusions
    by their output shapes: in the compiled decode program the experts'
    pattern matches THREE fusions an expert layer (the gate-and-up product,
    the activation, the down product: 24), all under the ``moe_experts``
    scope inside the walk's loop, and the mixer's FIVE fusions a convolution
    layer (the in-projection, the taps with the gate, the new row, the
    tail's slice and its write: 40), all under ``short_conv``; neither
    matches anything else, and the out-projection (the residual's shape) is
    in neither."""
    ops = verdict["lfm2_ops"]["lfm2_decode"]
    experts = ops["lfm2_expert_ffn_roofline"]
    assert len(experts) == 3 * 8, experts
    assert {name for name, _scope in experts} == {
        "fusion:fusion:f32[32,64,3584]", "fusion:fusion:bf16[2048,1792]",
        "fusion:fusion:f32[32,64,2048]"}
    assert all("/moe_experts/" in scope and "/while/" in scope
               for _name, scope in experts), experts
    mixer = ops["short_conv_roofline"]
    assert len(mixer) == 5 * 8, mixer
    assert {name for name, _scope in mixer} == {
        "fusion:fusion:f32[128,6144]", "fusion:fusion:bf16[1,128,2048]",
        "fusion:fusion:bf16[8,2,128,2048]",
        "multiply_reduce_fusion:fusion:f32[128,2048]",
        "slice_bitcast_fusion:fusion:bf16[2,128,2048]"}
    assert all("/short_conv/" in scope for _name, scope in mixer), mixer


@pytest.mark.parametrize("program,kernel,shape,need", [
    ("kimi_decode", "mla_decode_attn", "bf16[96,1,64,512]", (12.1e9, 12.4e9)),
    ("kimi_prefill_2048", "mla_prefill_attn", "bf16[1,128,1024,512]",
     (12.7e9, 13.1e9)),
    ("kimi_prefill_3072", "mla_prefill_attn", "bf16[1,192,1024,512]",
     (13.1e9, 13.5e9))])
def test_kimi_serve_programs_fit_the_chip(verdict, program, kernel, shape,
                                          need):
    """Kimi-K2.5's ``paged_decode`` and its two largest ``paged_prefill``
    buckets at the sizes of ``kimi-k2.5.agent-decode`` (``_default_buckets``
    ends with ``max_len`` itself, and the steady-state start submits prompts
    of up to 2,816 tokens, so the 3,072 bucket is compiled, warmed and used):
    they compile for a v5e, arguments plus temporaries stay under 15 GB (the
    check's float32 pass needs 0.78 GB of temporaries and 0.25 GB of logits
    beside the 12.14 GB resident), the latent kernels carry LongCat's names
    (the benchmark's ``mla_attn_*`` metrics match ``^mla_decode_attn:``),
    the expert layer is the grouped product and nothing copies or slices
    pool-shaped data. ``paged_attn_roofline``'s shape pattern matches no
    call of this family."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    # weights 9.70 GB + pool 2.44 GB, + temporaries 0.04 / 0.76 / 1.13 GB
    # (1.21 / 1.80 until the prefill's expert layer bounded its row buffer)
    low, high = need
    assert low < verdict["need_bytes"][program] < high < 15e9, \
        verdict["need_bytes"]
    found = verdict["kernels"][program]
    assert [s for n, s in found if n == kernel] == [shape], found
    assert {n for n, _s in found} == {kernel, "ragged-dot-none",
                                      "ragged-dot-metadata"}, found
    assert verdict["pool_movers"][program] == []
    assert not [n for n, s in found
                if re.search(PAGED_ATTN_PATTERN, f"{n}:custom-call:{s}")]


@pytest.mark.parametrize("program", [
    "kimi_prefill_2048", "kimi_prefill_3072", "longcat_prefill_1024",
    "trinity_prefill_8192", "mimo_prefill_4096"])
def test_a_prefills_expert_layer_holds_no_array_of_all_the_pairs(verdict,
                                                                 program):
    """``held_experts_ffn`` under its row bound: the compiled bucket writes
    no array of ``bucket x top-k`` rows by the model's width, an expert's or
    twice that (16,384 x 7,168 float32 was 470 MB, four times a layer, at
    Kimi's 2,048 bucket): the pairs it holds go through a buffer of the
    chip's share, and the combine sums a window's rows by token."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    assert verdict["pair_rows"][program] == []


def test_the_pair_row_scan_sees_a_decode_steps_buffer(verdict):
    """The same scan on Kimi's decode program, which keeps ``96 x 8`` rows
    (its share's bound would be no smaller): the grouped products' outputs
    and the rows gathered for them are found, so an empty list above is not
    the scan's blindness."""
    found = verdict["pair_rows"]["kimi_decode"]
    shapes = " ".join(shape for _op, _name, shape, _where in found)
    assert "f32[768,4096]" in shapes and "f32[768,7168]" in shapes, found


@pytest.mark.parametrize("program,steps,calls", [
    ("longcat_decode", [LONGCAT_SLOTS, 1], 8),
    ("kimi_decode", [KIMI_SLOTS, 1], 7),
    ("longcat_prefill_1024", [1, 64], 8),
    ("kimi_prefill_2048", [1, 128], 7),
    ("kimi_prefill_3072", [1, 192], 7)])
def test_latent_kernel_walks_the_table_itself(verdict, program, steps, calls):
    """The latent kernel's table is no grid axis either: a call is ``slots x
    query tiles`` grid steps (tiles of 16 queries x 64 heads), once a latent
    sublayer, the walk over a slot's live blocks a loop inside each whose one
    buffer fits the chip's scoped VMEM; the pool goes in where it lies. The
    call is a jit of its own: a program's sublayers share ONE traced body, so
    the kernel is lowered once a shape, not once a sublayer."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    assert verdict["grids"][program] == [steps] * calls
    assert verdict["latent_calls"][program] == [calls, 1]
    vmem = verdict["latent_vmem"][program]
    assert len(vmem) == calls and all(0 < v < V5E_SCOPED_VMEM for v in vmem), \
        vmem
    assert verdict["pool_movers"][program] == []


def test_kimi_decode_reads_its_weights_where_they_lie(verdict):
    """The two absorbed projections are stored heads-major, as the absorbed
    products read them (LongCat's decode re-lays 16 of them a call, above):
    neither ``ENTRY`` nor the loop's body writes a weight-sized array."""
    assert verdict["weight_movers"]["kimi_decode"] == []


def test_the_shared_experts_pattern_matches_its_products_alone(verdict):
    """``shared_expert_ms_per_step.batch`` finds the shared expert by the
    output shape of its gate and up products: in the compiled decode program
    the pattern matches two fusions an expert layer, both written under the
    ``moe_shared`` scope, and nothing else. (The prefill buckets' rows are
    not ``slots``; the metric counts inside the decode calls alone.)"""
    ops = verdict["shared_expert_ops"]["kimi_decode"]
    assert len(ops) == 2 * 6, ops
    assert sorted({name for name, _scope in ops}) == [
        "fusion:fusion:bf16[96,2048]", "fusion:fusion:f32[96,2048]"]
    assert all("/moe_shared/" in scope for _name, scope in ops), ops


def test_the_capacity_forms_pattern_matches_its_products_alone(verdict):
    """``expert_capacity_ffn_roofline`` and ``..._ms_per_step.batch`` find
    the routed experts' batched products by their output's shape: in the
    compiled decode program the pattern matches ONE fusion an expert layer
    (both products and the activation between them), written under the
    ``moe_experts`` scope, and nothing else; the grouped product is in the
    program too, behind the ``cond`` that an overflowing expert takes."""
    ops = verdict["capacity_ops"]["nemotron_decode"]
    assert len(ops) == 5, ops
    assert {name for name, _scope in ops} == {"fusion:fusion:f32[64,64,2688]"}
    assert all("/moe_experts/" in scope for _name, scope in ops), ops
    assert ["ragged-dot-none", "f32[768,1920]"] in verdict["kernels"][
        "nemotron_decode"]


def test_nemotron_decode_is_the_program_it_was(verdict):
    """The walk in passes is a prefill bucket's: the decode program (128
    slots x top-6: 64 rows an expert, the ``cond``) compiles to the
    instructions it had on PR 46's tree, so the fusion the capacity metrics
    name is the one they were accepted on. A PR that changes this family's
    decode program on purpose takes a new pair."""
    assert verdict["multisets"]["nemotron_decode"] == [
        10854, "72e88c77065503af"]


def test_the_state_kernels_operands_keep_their_three_parts(verdict):
    """``gdn_decode`` takes q, k and the gates as three bfloat16 parts each
    (``ops/layers.py:split3``), so that one bfloat16 MXU pass expands
    them exactly. The parts are cut by ``lax.reduce_precision``: written as
    float32 -> bfloat16 -> float32 casts XLA dropped them for the TPU and
    left q and k 8 bits, exact interpreted and wrong compiled, inside the
    cell's ``logit_tolerance`` (PERF.md, PR 31). The compiled decode program
    has to keep every one: nine for each of its twelve kernel calls."""
    calls, roundings = verdict["state_roundings"]["olmo_decode"]
    assert calls == 12 and roundings == 9 * calls, (calls, roundings)


def test_the_paged_attn_pattern_is_the_metric_files():
    with open(os.path.join(REPO, "benchmark", "metrics",
                           "paged_attn_roofline.json")) as f:
        assert json.load(f)["pattern"] == PAGED_ATTN_PATTERN


def test_pool_mover_scan_sees_what_the_old_layout_did():
    """The scan itself, on the instructions PR 24's trace named."""
    hlo = """
%fused_computation.3 (p0: bf16[4,1281,16,1024]) -> bf16[4,1281,16,1024] {
  %p0 = bf16[4,1281,16,1024]{3,2,1,0} parameter(0)
  ROOT %scatter.1 = bf16[4,1281,16,1024]{3,2,1,0} scatter(%p0, %i, %u)
}
%fused_computation.9 (p0: bf16[4,1281,16,1024]) -> bf16[1281,16,1024] {
  %p0 = bf16[4,1281,16,1024]{3,2,1,0} parameter(0)
  ROOT %slice.2 = bf16[1281,16,1024]{2,1,0} slice(%p0), slice={[1:2]}
}
ENTRY %main (k: bf16[4,1281,16,1024]) -> bf16[4,1281,16,1024] {
  %k = bf16[4,1281,16,1024]{3,2,1,0} parameter(0)
  %copy.1 = bf16[4,1281,16,1024]{3,2,1,0:T(8,128)(2,1)} copy(%k)
  %fusion.528 = bf16[4,1281,16,1024]{3,2,1,0} fusion(%copy.1), kind=kCustom, calls=%fused_computation.3
  %slice_bitcast_fusion.4 = bf16[1281,16,1024]{2,1,0} fusion(%fusion.528), kind=kLoop, calls=%fused_computation.9
  ROOT %tuple.1 = (bf16[4,1281,16,1024]{3,2,1,0}) tuple(%fusion.528)
}
"""
    found = pool_shaped_data_movers(hlo, 4, 1281, 16)
    assert sorted((op, name) for op, name, _shape in found) == [
        ("copy", "copy.1"), ("fusion", "slice_bitcast_fusion.4"),
        ("slice", "slice.2")]
    # a slot state [12, 48, 96, ...]: its in-place writes pass, a copy does not
    state = """
%fused_computation.1 (p0: f32[12,48,96,5760]) -> f32[12,48,96,5760] {
  %p0 = f32[12,48,96,5760]{3,2,1,0} parameter(0)
  ROOT %dynamic-update-slice.1 = f32[12,48,96,5760]{3,2,1,0} dynamic-update-slice(%p0, %u, %i)
}
ENTRY %main (s: f32[12,48,96,5760]) -> f32[12,48,96,5760] {
  %s = f32[12,48,96,5760]{3,2,1,0} parameter(0)
  %fusion.1 = f32[12,48,96,5760]{3,2,1,0} fusion(%s), kind=kLoop, calls=%fused_computation.1
  %gdn_decode.1 = (f32[12,48,96,5760]{3,2,1,0}, f32[48,1,5760]{2,1,0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{0}: (0, {})}
  %copy.7 = f32[12,48,96,5760]{3,2,1,0} copy(%s)
  ROOT %t = (f32[12,48,96,5760]{3,2,1,0}) tuple(%copy.7)
}
"""
    assert [(op, name) for op, name, _s in pool_shaped_data_movers(
        state, 12, 48, 96)] == [("copy", "copy.7")]



def test_weight_mover_scan_sees_what_the_per_call_cast_did():
    """The scan itself, on the instructions the parent's decode program held
    (compile-only, PR 32) and on what the compiler's prefetches look like."""
    hlo = """
%fused_computation.7 (p0: bf16[2,1024,4096]) -> (bf16[1024,4096], bf16[1024,4096]) {
  %p0 = bf16[2,1024,4096]{2,1,0} parameter(0)
  %slice.1 = bf16[1,1024,4096]{2,1,0} slice(%p0), slice={[0:1]}
  %bitcast.1 = bf16[1024,4096]{1,0} bitcast(%slice.1)
  %slice.2 = bf16[1,1024,4096]{2,1,0} slice(%p0), slice={[1:2]}
  %bitcast.2 = bf16[1024,4096]{1,0} bitcast(%slice.2)
  ROOT %tuple.9 = (bf16[1024,4096]{1,0}, bf16[1024,4096]{1,0}) tuple(%bitcast.1, %bitcast.2)
}
%fused_computation.8 (p0: bf16[36,1024], p1: bf16[1024,4096]) -> bf16[1024,4096] {
  %p0 = bf16[36,1024]{1,0} parameter(0)
  %p1 = bf16[1024,4096]{1,0} parameter(1)
  %convert.9 = f32[1024,4096]{1,0} convert(%p1)
  ROOT %multiply.1 = bf16[1024,4096]{1,0} multiply(%p1, %p1)
}
%body.3 (c: (s32[], bf16[1024,4096])) -> (s32[], bf16[1024,4096]) {
  %c = (s32[], bf16[1024,4096]{1,0}) parameter(0)
  %w = bf16[1024,4096]{1,0} get-tuple-element(%c), index=1
  %copy.5 = bf16[1024,4096]{0,1} copy(%w)
  ROOT %t = (s32[], bf16[1024,4096]{1,0}) tuple(%i, %w)
}
ENTRY %main (w: f32[2,1024,4096], e: f32[50304,1024], h: bf16[4096,1024]) -> bf16[36,4096] {
  %w = f32[2,1024,4096]{2,1,0} parameter(0)
  %e = f32[50304,1024]{1,0} parameter(1)
  %h = bf16[4096,1024]{1,0} parameter(2)
  %convert.37 = bf16[2,1024,4096]{2,1,0:T(8,128)(2,1)S(1)} convert(%w)
  %slice_bitcast_fusion.5 = (bf16[1024,4096]{1,0}, bf16[1024,4096]{1,0}) fusion(%convert.37), kind=kLoop, calls=%fused_computation.7
  %convert_element_type.175 = bf16[50304,1024]{1,0} convert(%e)
  %copy.29 = bf16[50304,1024]{0,1:T(8,128)(2,1)S(1)} copy(%convert_element_type.175)
  %fusion.77 = bf16[1024,4096]{1,0} fusion(%x, %slice_bitcast_fusion.5), kind=kLoop, calls=%fused_computation.8
  %slice-start.1 = ((bf16[4096,1024]{1,0}), bf16[1024,1024]{1,0:S(1)}, s32[]) slice-start(%h), slice={[0:1024], [0:1024]}
  %slice-done.1 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.1)
  %custom-call.14 = bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done.1), custom_call_target="ConcatBitcast"
  %copy-start.2 = (bf16[4096,1024]{1,0:S(1)}, bf16[4096,1024]{1,0}, u32[]) copy-start(%h)
  %copy-done.2 = bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.2)
  %copy-start.3 = (bf16[4096,1024]{1,0}, bf16[4096,1024]{1,0:S(1)}, u32[]) copy-start(%copy-done.2)
  %copy-done.3 = bf16[4096,1024]{1,0:T(8,128)(2,1)} copy-done(%copy-start.3)
  ROOT %while.1 = (s32[], bf16[1024,4096]{1,0}) while(%t0), condition=%cond.3, body=%body.3
}
"""
    shapes = weight_shapes([(2, 1024, 4096), (50304, 1024), (1024, 1024)],
                           [(1024, 4096), (4096, 1024)])
    assert (1024, 4096) in shapes and (1, 1024, 4096) in shapes
    assert (36, 1024) not in shapes                  # no activation's
    found = weight_shaped_data_movers(hlo, shapes)
    assert sorted((op, name, where) for op, name, _s, where in found) == [
        ("convert", "convert.37", "ENTRY"),
        ("convert", "convert_element_type.175", "ENTRY"),
        ("copy", "copy.29", "ENTRY"),
        ("copy", "copy.5", "body.3"),                # once a token step
        ("copy-done", "copy-done.3", "ENTRY"),       # back out to HBM
        ("fusion", "slice_bitcast_fusion.5", "ENTRY")]
    # not: a fusion that computes (fusion.77), anything inside a fusion's
    # body (convert.9, slice.1), the prefetches into VMEM (slice-done.1,
    # custom-call.14, copy-done.2) nor what starts one.


if __name__ == "__main__":
    print(json.dumps(compile_all(*(int(arg) for arg in sys.argv[1:3]))))


@pytest.mark.parametrize("program,kernels,need", [
    # the selection's kernel takes tiles of 32 rows: 48 slots are two, padded
    ("glm_decode", {"dsa_select": "bf16[64,8192]",
                    "mla_decode_attn": "bf16[48,1,64,512]"}, (8.3e9, 8.7e9)),
    ("glm_prefill_8192", {"dsa_select_prefill": "bf16[256,8192]",
                          "mla_prefill_attn": "bf16[1,512,1024,512]"},
     (10.3e9, 10.8e9))])
def test_glm_serve_programs_fit_the_chip(verdict, program, kernels, need):
    """GLM-5's ``paged_decode`` and its largest ``paged_prefill`` at the
    sizes of ``glm-5.sparse-decode`` (48 slots, 21,937 blocks in both pool
    arrays): they compile for a v5e, the latent kernel WITH its keep-bit
    operand and the selection's kernel among them (a v5e compares no
    bfloat16: the bits are widened first), and arguments plus temporaries
    stay under ISSUE 53's 14.5 GB: weights 5.41 GB + the two pools 2.70 GB,
    + 0.36 GB of temporaries in decode and 2.44 GB in the 8,192 bucket, whose
    index scores and keep bits are made 256 queries at a time (whole, the 32
    heads' products are 8.6 GB). No instruction copies or slices data the
    size of a pool. Of the weights the decode program moves ONE matrix a
    layer, ``W_qb`` [2048, 64, 256], and that is the COMPILER'S: it wants the
    query product's operand heads-major, copies each layer's once a CALL
    before the loop of 8 steps (335 MB beside the ~45 GB a call reads) and
    parks layer 0's in its alternate memory (as MiMo-V2's two, PERF.md 7 item
    31). The indexer's ``w_q`` is stored a plain ``[r, heads x head_dim]``
    matrix: stored ``[r, heads, head_dim]`` it was re-laid on every STEP."""
    assert verdict["programs"][program] == "ok", verdict["programs"][program]
    assert need[0] < verdict["need_bytes"][program] < need[1], verdict["need_bytes"]
    assert verdict["need_bytes"][program] < 14.5e9
    found = {n: s for n, s in verdict["kernels"][program]
             if not n.startswith("ragged-dot")}
    assert found == kernels, verdict["kernels"][program]
    assert verdict["pool_movers"][program] == []
    if program == "glm_decode":
        moved = sorted((shape.split("{")[0], where == "ENTRY") for _op, _name,
                       shape, where in verdict["weight_movers"][program])
        assert moved == [("bf16[2048,64,256]", False)] + [
            ("bf16[2048,64,256]", True)] * 5, moved
    vmem = verdict["scoped_vmem"][program]
    assert max(vmem) > 1 << 20 and all(0 <= v < V5E_SCOPED_VMEM for v in vmem)
    # one call of each a layer: 5 latent, 5 selections
    assert len(verdict["grids"][program]) == 10, verdict["grids"][program]


def test_the_selections_patterns_match_its_operations_alone(verdict):
    """The selection's metrics find what XLA runs by kind and shape: in the
    compiled decode program ``dsa_index_ms_per_step.batch`` matches ONE
    fusion a layer (the scores' product with the ReLU, the head weights and
    the sum over heads) under the ``dsa_index_scores`` scope,
    ``dsa_gather_ms_per_step.batch`` one (the index keys through the table)
    under ``dsa_gather``, ``dsa_select_ms_per_step.batch`` the kernel; and
    ``sparse_attn_roofline`` those three and the latent kernel, nothing
    else. In the prefill program none of them matches anything: its kernels
    carry other names and its fusions other shapes. The prefill's three
    likewise, the other way about: in the 8,192 bucket the scores of a block
    of 256 queries are one fusion a layer under the same scope, the selection
    and the latent kernel one call a layer under a prompt's names, and in the
    decode program they match nothing."""
    ops = verdict["dsa_ops"]["glm_decode"]
    by = {m: sorted({name for name, _scope in found}) for m, found in ops.items()}
    assert by["dsa_index_ms_per_step.batch"] == ["fusion:fusion:f32[48,8192]"]
    assert by["dsa_gather_ms_per_step.batch"] == [
        "fusion:fusion:bf16[24576,16,128]"]
    assert by["dsa_select_ms_per_step.batch"] == [
        "dsa_select:custom-call:bf16[64,8192]"]
    for metric, scope in (("dsa_index_ms_per_step.batch", "/dsa_index_scores/"),
                          ("dsa_gather_ms_per_step.batch", "/dsa_gather/"),
                          ("dsa_select_ms_per_step.batch", "/dsa_select/")):
        assert len(ops[metric]) == 5, ops[metric]
        assert all(scope in s for _name, s in ops[metric]), ops[metric]
    assert by["sparse_attn_roofline"] == sorted(
        by["dsa_index_ms_per_step.batch"] + by["dsa_gather_ms_per_step.batch"]
        + by["dsa_select_ms_per_step.batch"]
        + ["mla_decode_attn:custom-call:bf16[48,1,64,512]"])
    assert len(ops["sparse_attn_roofline"]) == 20
    prefill = verdict["dsa_ops"]["glm_prefill_8192"]
    assert all(prefill[m] == [] for m in DSA_METRICS)
    assert all(ops[m] == [] for m in DSA_PREFILL_METRICS)
    for metric, name, scope in (
            ("dsa_index_ms_per_prefill.batch", "fusion:fusion:f32[256,8192]",
             "/dsa_index_scores/"),
            ("dsa_select_ms_per_prefill.batch", "dsa_select_prefill:", "/dsa_select/"),
            ("mla_attn_ms_per_prefill.batch", "mla_prefill_attn:", "/attn_sparse/")):
        assert len(prefill[metric]) == 5, prefill[metric]
        assert all(n.startswith(name) and scope in s
                   for n, s in prefill[metric]), prefill[metric]
