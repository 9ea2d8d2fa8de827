"""The benchmark's tests import it as the package ``bench`` from the
checkout's root."""
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
