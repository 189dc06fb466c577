"""batch program: device time of the one ``measure_texture`` call (cells on Actin): the per-object stretch, the GLCM contraction and the 13 Haralick features, inside the executions of the
batch program in the traced unit, over the unit's sites.  By the pipeline
module in each instruction's ``tf_op`` (the first scope after the jitted
function's own), every stage of it, so a layout change or a convert that
carries the module's name and no stage name is in here and in
``stage_other_ms_per_site``, not in ``stage_measure_ms_per_site``.  What
the four ``measure_*_ms_per_site`` leave of ``stage_measure`` unexplained is
a measure scope entered from another module (none today); what they leave
of ``program_ms_per_site`` is the segmentation modules and the operations
that carry no ``tf_op`` at all (copies, iotas)."""

from benchmark import roofline_measure

UNIT = "ms/site"


def read(run):
    return roofline_measure.module_ms_per_site(run, "measure_texture")
