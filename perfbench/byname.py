"""Code found by name. A model family, a kernel's arithmetic, a job kind and
a reader are each a module ``perfbench/<kind>/<name>.py``, imported through
its package like any other, so that a later PR adds one as a new file. (The
tests add theirs to a throw-away copy and put the copy's folder on the
package's ``__path__``.) A name with no file is an error that lists the
names there are."""

import importlib
import pkgutil


class BenchError(Exception):
    """A run that cannot give a result; the message names what is wrong."""


def module(kind: str, name: str):
    """The module ``perfbench.<kind>.<name>``; ``kind`` is ``families``,
    ``kernels``, ``jobs`` or ``readers``."""
    package = importlib.import_module(f"perfbench.{kind}")
    known = sorted({m.name for m in pkgutil.iter_modules(package.__path__)})
    if name not in known:
        raise BenchError(f"perfbench/{kind}/ has no {name!r}; known: {known}")
    return importlib.import_module(f"perfbench.{kind}.{name}")
