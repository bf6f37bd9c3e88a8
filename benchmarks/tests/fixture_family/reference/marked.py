"""The fixture family's plain reference.  The program has no second family
to serve, so the arithmetic is the dense reference's, loaded from its file;
``compare`` marks its answer, so that a run directory shows whose reference
decided ``correct``."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "dense", os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)

draw_weights = _dense.draw_weights
make_forward = _dense.make_forward
reference_logprobs = _dense.reference_logprobs
control_answers = _dense.control_answers


def compare(answers, ref_lps) -> dict:
    return dict(_dense.compare(answers, ref_lps), marked_by="fixture_family/reference/marked.py")
