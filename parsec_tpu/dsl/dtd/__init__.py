"""DTD front-end: dynamic task discovery (insert_task).

reference: parsec/interfaces/dtd/ — see insert.py in this package.
"""

from parsec_tpu.dsl.dtd.insert import (AFFINITY, DONT_TRACK, INOUT,  # noqa: F401
                                       INPUT, OUTPUT, PULLIN, PUSHOUT,
                                       SCRATCH, VALUE, DTDTaskClass,
                                       DTDTaskpool, DTDTile, Region,
                                       create_task_class)
