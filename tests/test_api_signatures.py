"""API-freeze check (reference: tools/check_api_approvals.sh +
print_signatures.py): the public signature dump must match the checked-in
snapshot; intentional changes regenerate it with
`PYTHONPATH=. python tools/print_signatures.py > tests/api_signatures.txt`."""
import os
import importlib.util

_HERE = os.path.dirname(__file__)
_TOOL = os.path.join(_HERE, "..", "tools", "print_signatures.py")
_SNAP = os.path.join(_HERE, "api_signatures.txt")


def _load_tool():
    spec = importlib.util.spec_from_file_location("print_signatures", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_api_signatures_frozen():
    current = sorted(set(_load_tool().iter_api()))
    with open(_SNAP) as f:
        snapshot = [ln.rstrip("\n") for ln in f if ln.strip()]
    removed = sorted(set(snapshot) - set(current))
    added = sorted(set(current) - set(snapshot))
    msg = []
    if removed:
        msg.append("REMOVED/CHANGED (breaks users):\n  " +
                   "\n  ".join(removed[:40]))
    if added:
        msg.append("ADDED (regenerate the snapshot to bless):\n  " +
                   "\n  ".join(added[:40]))
    assert not removed and not added, (
        "public API drifted from tests/api_signatures.txt — if "
        "intentional, run `python tools/print_signatures.py > "
        "tests/api_signatures.txt`\n" + "\n".join(msg))


def test_api_surface_is_substantial():
    # the snapshot is a real freeze, not an empty file
    with open(_SNAP) as f:
        n = sum(1 for ln in f if ln.strip())
    assert n > 800, n
