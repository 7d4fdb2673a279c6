"""isolint configuration: the names each pass keys on, and the budgets.

Everything here is data, not code, so tightening the analyzer is an edit
to this file (or a CLI flag for the budget), not a rewrite of a pass.
The names mirror the repo's enforcement surface — update them when the
checked egress API grows a new entry point.
"""
from __future__ import annotations

import re

# -- pass 1: egress-bypass taint --------------------------------------------

# Method names whose call on a pool-like receiver creates a tainted value.
POOL_SOURCE_METHODS = {"tensor", "region"}

# A receiver is pool-like when its name matches this, or when it was
# assigned from a SharedTensorPool(...) constructor in the same scope.
POOL_NAME_HINT = re.compile(r"pool", re.IGNORECASE)
POOL_CONSTRUCTORS = {"SharedTensorPool"}

# Calls that count as THE checked egress path: a tainted value passed as an
# argument to one of these (matched on the call's final name segment) has
# reached the Permission Checker.
CHECKED_SINKS = {
    "checked_gather",
    "checked_memcrypt",            # kernels.ref oracle composition
    "checked_memcrypt_pallas",
    "checked_memcrypt_view_pallas",
    "fabric_egress_pallas",
    "check",                       # HostRuntime.check
    "check_access",
    "check_access_jit",
    "cached_check_access",
    "cached_check_access_jit",
    "step_egress",                 # ShardedFabric.step_egress
}

# Functions that ARE the enforcement layer: their bodies legitimately read
# the pool raw (the read is followed by the check they implement), so pass 1
# skips them instead of demanding a pragma inside the checker itself.
TRUSTED_EGRESS_IMPLS = {"checked_gather"}

# Attribute reads on tainted values that are metadata, not data egress.
TAINT_SAFE_ATTRS = {"shape", "dtype", "ndim", "size", "start_page",
                    "n_pages", "rows", "row_shape", "bytes_per_row",
                    "pages_for_rows", "name"}

# -- pass 2: fence discipline ------------------------------------------------

# Method names that commit/broadcast permission-state changes (bus.publish
# and every FM/fabric entry point that bumps the table epoch + publishes).
PUBLISH_METHODS = {"publish", "propose", "revoke_hwpid", "revoke_range",
                   "admit", "evict", "grant_shared", "vacuum", "commit"}

# Method names that close the BISnp fence (advance host observation).
FENCE_METHODS = {"deliver", "deliver_until", "quiesce", "drain",
                 "sync_host", "restart"}

# Calls that consume PermCache / fabric-view state and therefore must not
# run between a publish and a fence in the same flow.
CACHE_CONSUMERS = {"cached_check_access", "cached_check_access_jit",
                   "check", "step_egress"}

# Check entry points that must default-deny: each must reference a FAULT_*
# constant other than FAULT_NONE, or delegate to another entry point /
# verdict assembler that does.
CHECK_ENTRY_POINTS = {"check_access", "cached_check_access", "check",
                      "desync_check_result"}
FAULT_DELEGATES = {"_finalize", "desync_check_result", "check_access",
                   "cached_check_access", "cached_check_access_jit",
                   "checked_gather"}
FAULT_PREFIX = "FAULT_"
FAULT_BENIGN = {"FAULT_NONE"}

# -- pass 3: pallas kernel budget --------------------------------------------

# Per-grid-step VMEM budget (bytes).  TPU cores carry ~16 MiB of VMEM; the
# gate sits at a quarter of that so one kernel's operand set (double-
# buffered) leaves room for the compiler's own spills and the next kernel's
# prologue.  Override with --vmem-budget.
VMEM_BUDGET_BYTES = 4 * 1024 * 1024

# Worst-case bindings for shape symbols the evaluator cannot resolve from
# the source (dynamic dims).  These are the architectural ceilings the repo
# itself documents: MAX_ENTRIES-padded shards, SUPER_BLOCKS*BLOCK super
# blocks, the 255-host fabric, 128-lane head dims.
WORST_CASE_DIMS = {
    "np_": 65536,        # padded per-shard entries (permcheck.MAX_ENTRIES)
    "n_tiles": 64,       # MAX_ENTRIES // ENTRY_TILE
    "sb": 8192,          # SUPER_BLOCKS * BLOCK words per fused grid step
    "h": 255,            # paper's host ceiling (fabric kernel row count)
    "dh": 128,           # attention head dim (flash kernel)
    "b": 8,              # flash batch (block dim is 1 anyway)
    "n_k": 64,           # flash K-step count (grid extent, not a block dim)
}

# Helpers that build a pallas_call's compiler params from the grid's
# dimension semantics, passed as string arguments
# (``**compiler_params(interpret, "parallel")``).
COMPILER_PARAMS_HELPERS = {"compiler_params"}

# Element width assumed for BlockSpec operands whose dtype is not statically
# visible (BlockSpec carries shape only).  Every egress kernel in this repo
# moves u32/i32/f32 words; out_specs widths come from the paired
# jax.ShapeDtypeStruct when parseable.
DEFAULT_ITEMSIZE = 4
DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1,
}

# Maps the repo's import roots to source directories so the shape evaluator
# can resolve constants imported across modules (e.g. permcheck.ENTRY_TILE
# re-used by memcrypt/fabric_egress).
MODULE_ROOTS = {"repro": "src/repro"}

# jax.jit(lambda ...) closure-capture detection: a free name bound in the
# enclosing scope by one of these producers is an array that XLA will
# constant-fold into the jitted computation.
ARRAY_PRODUCER_ROOTS = {"jnp", "np"}
ARRAY_PRODUCER_CALLS = {"to_device", "make_hwpid_local", "make_shard_view",
                        "table_shard_view", "grant_sizes", "asarray",
                        "array", "arange", "zeros", "ones", "full",
                        "normal", "integers"}

# -- CLI defaults ------------------------------------------------------------

DEFAULT_SCOPES = ("src", "examples", "benchmarks")
DEFAULT_BASELINE = "tools/isolint/baseline.json"
