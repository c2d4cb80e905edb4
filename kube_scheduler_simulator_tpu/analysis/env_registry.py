"""env-registry analyzer (KSS101-103): the ``KSS_*`` env surface.

utils/envcheck.py's ``KNOWN`` registry is the one catalogue of KSS_*
configuration — boot validation, typo detection, and the
docs/environment-variables.md tables all stand on it. The contract has
three directions, each its own rule:

  KSS101  an environment READ of a ``KSS_*`` name anywhere in the
          package that the registry does not declare (the knob works
          but boot validation rejects it — or worse, typo detection
          flags every legitimate use);
  KSS102  a registered name nothing reads (dead config: validation
          blesses a knob the runtime ignores);
  KSS103  a registered name docs/environment-variables.md never
          mentions (an operator cannot discover it).

Read-site extraction is AST-based and covers the repo's three idioms:
direct reads (``os.environ.get("KSS_X")``, ``os.getenv``, subscripts,
``env.get`` on an env-shaped mapping), module-level name constants
(``ENV_VAR = "KSS_TRACE"`` then ``os.environ.get(ENV_VAR)``), and
module-local reader helpers whose *parameter* is the variable name
(``_env_number(name, ...)`` in utils/broker.py, ``_env_int(env, name,
...)`` in server/sessions.py). Underscore-prefixed names (``_KSS_*``)
would be process-internal plumbing, not operator configuration, and
are out of scope.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, RepoContext, SourceFile, SourceTree

ENVCHECK_REL = "utils/envcheck.py"
_NAME_RE = re.compile(r"^KSS_[A-Z0-9_]+$")

# receivers that read the process environment: `os.environ`/`environ`
# attributes, or a bare name conventionally bound to one (the
# `env = os.environ if env is None else env` idiom)
_ENV_RECEIVER_NAMES = ("env", "environ")


def _module_consts(tree: ast.Module) -> "dict[str, str]":
    """Module-level ``NAME = "literal"`` bindings."""
    out: dict[str, str] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            out[node.targets[0].id] = node.value.value
    return out


def _resolve_name_expr(expr: ast.expr, consts: "dict[str, str]") -> "str | None":
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.Name):
        return consts.get(expr.id)
    return None


def _is_env_receiver(expr: ast.expr) -> bool:
    """True for `os.environ`, bare `environ`, or an env-named mapping."""
    if isinstance(expr, ast.Attribute):
        return expr.attr == "environ"
    if isinstance(expr, ast.Name):
        return expr.id in _ENV_RECEIVER_NAMES
    return False


def _param_names(fn: "ast.FunctionDef | ast.AsyncFunctionDef") -> "list[str]":
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args]


def _reader_helpers(
    tree: ast.Module,
) -> "dict[str, int]":
    """Module-local functions that read the environment through one of
    their parameters: {function name: index of the name parameter}."""
    out: dict[str, int] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = _param_names(node)
        for inner in ast.walk(node):
            name_expr: "ast.expr | None" = None
            if (
                isinstance(inner, ast.Call)
                and isinstance(inner.func, ast.Attribute)
                and inner.func.attr in ("get", "pop")
                and _is_env_receiver(inner.func.value)
                and inner.args
            ):
                name_expr = inner.args[0]
            elif isinstance(inner, ast.Subscript) and _is_env_receiver(inner.value):
                name_expr = inner.slice
            if (
                name_expr is not None
                and isinstance(name_expr, ast.Name)
                and name_expr.id in params
            ):
                out[node.name] = params.index(name_expr.id)
                break
    return out


def _read_sites(sf: SourceFile) -> "list[tuple[str, int]]":
    """(KSS_* name, lineno) for every environment read in the module."""
    consts = _module_consts(sf.tree)
    helpers = _reader_helpers(sf.tree)
    sites: list[tuple[str, int]] = []

    def note(expr: "ast.expr | None", lineno: int) -> None:
        if expr is None:
            return
        name = _resolve_name_expr(expr, consts)
        if name is not None and _NAME_RE.match(name):
            sites.append((name, lineno))

    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call):
            fn = node.func
            # os.environ.get(X) / env.get(X) / os.environ.pop(X)
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr in ("get", "pop")
                and _is_env_receiver(fn.value)
                and node.args
            ):
                note(node.args[0], node.lineno)
            # os.getenv(X)
            elif (
                isinstance(fn, ast.Attribute)
                and fn.attr == "getenv"
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "os"
                and node.args
            ):
                note(node.args[0], node.lineno)
            # module-local reader helper: _env_number("KSS_X", ...)
            elif isinstance(fn, ast.Name) and fn.id in helpers:
                idx = helpers[fn.id]
                if idx < len(node.args):
                    note(node.args[idx], node.lineno)
        elif isinstance(node, ast.Subscript) and _is_env_receiver(node.value):
            # os.environ[X] — reads and writes both tie the name to the
            # runtime, so both must be declared
            note(node.slice, node.lineno)
    return sites


def registry_names(tree: SourceTree) -> "dict[str, int]":
    """The envcheck ``KNOWN`` registry: {name: lineno}. Empty when the
    tree carries no envcheck module (synthetic negative-test trees)."""
    sf = tree.get(ENVCHECK_REL)
    if sf is None:
        return {}
    for node in sf.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target: "ast.expr | None" = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            target = node.target
        else:
            continue
        if (
            isinstance(target, ast.Name)
            and target.id == "KNOWN"
            and isinstance(getattr(node, "value", None), ast.Dict)
        ):
            return {
                k.value: k.lineno
                for k in node.value.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)
            }
    return {}


def run(tree: SourceTree, repo: RepoContext) -> "list[Finding]":
    known = registry_names(tree)
    findings: list[Finding] = []
    reads: dict[str, list[tuple[str, int]]] = {}
    for sf in tree.files:
        if sf.rel == ENVCHECK_REL:
            continue  # the registry module reads every name generically
        for name, lineno in _read_sites(sf):
            reads.setdefault(name, []).append((sf.rel, lineno))

    for name in sorted(reads):
        if name not in known:
            rel, lineno = reads[name][0]
            findings.append(
                Finding(
                    "KSS101",
                    rel,
                    lineno,
                    f"environment read of {name} is not declared in "
                    f"utils/envcheck.KNOWN",
                    hint=f"add {name} with a validator to the KNOWN registry "
                    f"(and a row to docs/environment-variables.md)",
                )
            )
    for name, lineno in sorted(known.items()):
        if name not in reads:
            findings.append(
                Finding(
                    "KSS102",
                    ENVCHECK_REL,
                    lineno,
                    f"registered variable {name} is never read by the "
                    f"package (dead config)",
                    hint="wire the knob into the runtime or drop the "
                    "registry entry + its docs row",
                )
            )
    doc = repo.doc_text("environment-variables.md")
    if doc is not None:
        for name, lineno in sorted(known.items()):
            if name not in doc:
                findings.append(
                    Finding(
                        "KSS103",
                        ENVCHECK_REL,
                        lineno,
                        f"registered variable {name} is missing from "
                        f"docs/environment-variables.md",
                        hint="add a row to the matching table in "
                        "docs/environment-variables.md",
                    )
                )
    return findings
