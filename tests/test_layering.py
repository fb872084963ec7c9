"""Import layering: ``repro.core`` is the lowest layer of the mapper.

The mapping pipeline, the hierarchy, the serve layer and the mesh
builder are built on ``repro.core``; no module of ``core`` may import
them back.  The check reads every import statement of every ``core``
module with ``ast``, function-local imports included, so a deferred
import that keeps ``import repro.core`` clean still fails it.
"""

import ast
import pathlib

import repro.core

HIGHER = ("repro.mapping", "repro.hier", "repro.serve", "repro.meshmap")
_CORE = pathlib.Path(repro.core.__file__).parent


def _imported_modules(path: pathlib.Path):
    """Every module an import statement of ``path`` names, resolved to
    an absolute dotted name (``from .. import x`` -> ``repro.x``)."""
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                sub = path.relative_to(_CORE).parent.parts
                pkg = ["repro", "core", *sub]
                base = pkg[: len(pkg) - node.level + 1]
                mod = ".".join(base + ([node.module] if node.module
                                       else []))
            else:
                mod = node.module or ""
            yield node.lineno, mod
            # ``from repro import mapping`` names the package itself
            for alias in node.names:
                yield node.lineno, f"{mod}.{alias.name}"


def test_core_imports_no_higher_layer():
    modules = sorted(_CORE.rglob("*.py"))
    assert modules, _CORE
    bad = [f"{p.relative_to(_CORE)}:{line}: {mod}"
           for p in modules for line, mod in _imported_modules(p)
           if any(mod == h or mod.startswith(h + ".") for h in HIGHER)]
    assert not bad, "core imports a higher layer:\n" + "\n".join(bad)
