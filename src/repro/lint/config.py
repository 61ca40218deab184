"""Configuration for ``repro.lint``: defaults + the pyproject
``[tool.repro-lint]`` table.

The interpreter this repo pins predates ``tomllib`` (3.11), and adding a
TOML dependency is off the table, so :func:`_parse_toml_table` hand-rolls
the small TOML subset the lint table actually needs — ``key = value`` with
string / int / float / bool scalars and (possibly multiline) arrays of
them.  Everything outside the requested table is skipped, not parsed.
"""
from __future__ import annotations

import dataclasses
import os
import re


@dataclasses.dataclass(frozen=True)
class LintConfig:
    """Knobs for the rule families (pyproject ``[tool.repro-lint]``)."""

    #: rule-id filters applied before per-line suppressions
    select: tuple = ()
    ignore: tuple = ()

    #: files (repo-relative suffixes) on the pipelined dispatch path —
    #: the §5 O(1)-sync discipline (SYNC001/002) is enforced only here
    sync_modules: tuple = (
        "repro/core/executor.py",
        "repro/core/engine.py",
        "repro/core/distributed.py",
        "repro/core/scheduler.py",
        "repro/serve/broker.py",
    )
    #: dispatcher-protocol methods the executor only calls *after* blocking
    #: on ``Dispatch.out`` (see ``repro.core.executor.BatchDispatcher``) —
    #: host reads inside them are post-sync by contract
    post_sync_functions: tuple = (
        "count", "marshal", "tile_stats", "retry_capacity",
    )
    #: call names whose results are device arrays (taint roots beyond the
    #: ``jnp.``/``jax.``/``pl.`` namespaces)
    #: (``submit``/``wait`` cover the scheduler's worker-call futures —
    #: handles to device-bound group work, whose ``.result()`` blocks)
    device_calls: tuple = (
        "query_block", "dispatch", "redispatch", "_launch", "_fn",
        "interaction_tiles", "distthresh_pallas", "distthresh_compact_pallas",
        "distthresh_compact_live_pallas", "pallas_call", "submit", "wait",
    )
    #: attribute names that hold device arrays (``Dispatch.out``)
    device_attrs: tuple = ("out",)

    #: files holding Pallas kernels (KERN rules)
    kern_modules: tuple = (
        "repro/kernels/distthresh.py",
        "repro/kernels/ops.py",
        )
    #: grid-spec constructor names whose leading kernel params are
    #: scalar-prefetch refs — the live-tile-list contract (KERN006) is
    #: enforced on kernels launched through them
    prefetch_grid_specs: tuple = ("PrefetchScalarGridSpec",)
    #: static VMEM budget per kernel invocation, MiB (KERN005)
    vmem_budget_mib: int = 16
    #: live-copy multiplier for the VMEM estimate (double buffering)
    vmem_multiplier: int = 2

    #: import-graph roots for DEAD001 (module names, plus every module
    #: imported from the files under ``dead_root_dirs``)
    dead_roots: tuple = ("repro.api", "repro.serve")
    dead_root_dirs: tuple = ("tests", "benchmarks")
    #: modules never reported (e.g. kept deliberately as examples)
    dead_ignore: tuple = ()


_SCALAR_RES = (
    (re.compile(r'^"((?:[^"\\]|\\.)*)"$'), lambda m: m.group(1)
        .replace('\\"', '"').replace("\\\\", "\\")),
    (re.compile(r"^'([^']*)'$"), lambda m: m.group(1)),
    (re.compile(r"^(true|false)$"), lambda m: m.group(1) == "true"),
    (re.compile(r"^[+-]?\d+$"), lambda m: int(m.group(0))),
    (re.compile(r"^[+-]?\d*\.\d+$"), lambda m: float(m.group(0))),
)


def _parse_scalar(text: str):
    text = text.strip()
    for pattern, conv in _SCALAR_RES:
        m = pattern.match(text)
        if m:
            return conv(m)
    raise ValueError(f"unsupported TOML value: {text!r}")


def _strip_comment(line: str) -> str:
    """Drop a trailing comment (quote-aware enough for this subset)."""
    out, in_str, quote = [], False, ""
    for ch in line:
        if in_str:
            out.append(ch)
            if ch == quote:
                in_str = False
        elif ch in "\"'":
            in_str, quote = True, ch
            out.append(ch)
        elif ch == "#":
            break
        else:
            out.append(ch)
    return "".join(out)


def _parse_toml_table(text: str, table: str) -> dict:
    """The ``[table]`` section of a TOML document as a plain dict.

    Supports exactly the subset the lint table uses: scalar values and
    arrays of scalars, arrays possibly spanning multiple lines.  Unknown
    syntax inside the table raises; everything outside it is ignored.
    """
    header = re.compile(r'^\[(?:"([^"]+)"|([^\]]+))\]\s*$')
    out: dict = {}
    in_table = False
    pending_key = None
    pending_chunks: list = []

    def flush_array():
        nonlocal pending_key, pending_chunks
        body = " ".join(pending_chunks).strip()
        assert body.startswith("[") and body.endswith("]"), body
        inner = body[1:-1].strip()
        items = []
        if inner:
            depth = 0
            chunk = ""
            for ch in inner:
                if ch == "," and depth == 0:
                    if chunk.strip():
                        items.append(_parse_scalar(chunk))
                    chunk = ""
                else:
                    if ch in "\"'":
                        depth ^= 1
                    chunk += ch
            if chunk.strip():
                items.append(_parse_scalar(chunk))
        out[pending_key] = items
        pending_key, pending_chunks = None, []

    for raw in text.splitlines():
        line = _strip_comment(raw).rstrip()
        if not line.strip():
            continue
        m = header.match(line.strip()) if not line[0].isspace() else None
        if m and pending_key is None:
            in_table = (m.group(1) or m.group(2)).strip() == table
            continue
        if not in_table:
            continue
        if pending_key is not None:
            pending_chunks.append(line.strip())
            if line.rstrip().endswith("]"):
                flush_array()
            continue
        if "=" not in line:
            raise ValueError(f"unparseable line in [{table}]: {raw!r}")
        key, _, value = line.partition("=")
        key = key.strip().strip('"')
        value = value.strip()
        if value.startswith("["):
            pending_key, pending_chunks = key, [value]
            if value.endswith("]"):
                flush_array()
        else:
            out[key] = _parse_scalar(value)
    return out


def load_config(root: str | None = None) -> LintConfig:
    """Defaults overlaid with ``[tool.repro-lint]`` from ``root``'s
    pyproject.toml (searched upward from the cwd when ``root`` is None)."""
    path = None
    base = os.path.abspath(root or os.getcwd())
    probe = base
    for _ in range(8):
        cand = os.path.join(probe, "pyproject.toml")
        if os.path.isfile(cand):
            path = cand
            break
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    if path is None:
        return LintConfig()
    with open(path, encoding="utf-8") as fh:
        table = _parse_toml_table(fh.read(), "tool.repro-lint")
    fields = {f.name: f for f in dataclasses.fields(LintConfig)}
    kwargs = {}
    for key, value in table.items():
        name = key.replace("-", "_")
        if name not in fields:
            raise ValueError(f"unknown [tool.repro-lint] key: {key!r}")
        if isinstance(value, list):
            value = tuple(value)
        kwargs[name] = value
    return LintConfig(**kwargs)
