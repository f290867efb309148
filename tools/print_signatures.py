"""Public-API signature dump (reference: tools/print_signatures.py, used
by tools/check_api_approvals.sh to freeze the API surface).

Prints one `module.symbol(signature)` line per public callable of the
curated module list; `tests/test_api_signatures.py` diffs this against the
checked-in snapshot so accidental API breaks fail CI.  Regenerate after an
INTENTIONAL change with:

    python tools/print_signatures.py > tests/api_signatures.txt
"""
from __future__ import annotations

import enum
import inspect
import sys

MODULES = [
    "paddle_tpu",
    "paddle_tpu.static",
    "paddle_tpu.nn",
    "paddle_tpu.nn.functional",
    "paddle_tpu.tensor",
    "paddle_tpu.optimizer",
    "paddle_tpu.io",
    "paddle_tpu.jit",
    "paddle_tpu.amp",
    "paddle_tpu.metric",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.fleet",
    "paddle_tpu.distributed.fleet_control",
    "paddle_tpu.distributed.tensor_parallel",
    "paddle_tpu.inference",
    "paddle_tpu.serving",
    "paddle_tpu.checkpoint",
    "paddle_tpu.observability",
    "paddle_tpu.slim",
    "paddle_tpu.incubate",
]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def iter_api():
    import importlib
    for mod_name in MODULES:
        mod = importlib.import_module(mod_name)
        public = getattr(mod, "__all__", None)
        if public is None:
            public = [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(set(public)):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            if inspect.isclass(obj) and issubclass(obj, enum.Enum):
                # an Enum's surface is its members; its constructor is
                # `Enum.__call__`, inherited, and its signature differs by
                # Python version
                yield f"{mod_name}.{name}[{', '.join(obj.__members__)}]"
            elif inspect.isclass(obj):
                yield f"{mod_name}.{name}{_sig(obj.__init__)}"
                for m_name, m in sorted(vars(obj).items()):
                    if m_name.startswith("_") or not callable(m):
                        continue
                    yield f"{mod_name}.{name}.{m_name}{_sig(m)}"
            elif callable(obj):
                yield f"{mod_name}.{name}{_sig(obj)}"


def main():
    for line in sorted(set(iter_api())):
        sys.stdout.write(line + "\n")


if __name__ == "__main__":
    main()
