"""Trinity-Mini (AFMoE): the benchmark's own tests of the architecture
(``benchmark/tests/test_afmoe_reference.py``: the reference's proofs, the
file against the catalog, the counts, the adapter's refusals), collected
here under their own names, no body copied, because ``benchmark/tests`` is
not in tier-1's path. A file of its own beside ``tests/test_trinity.py``
(the program against that reference) so that ``--dist loadfile`` gives the
two halves to two workers: together they were the run's last file to end."""

from benchmark.tests import test_afmoe_reference as _reference_tests
from tests.harness_controls import pin_path_hash

pin_path_hash(_reference_tests)  # ``unsettle`` draws by a leaf's path
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj
