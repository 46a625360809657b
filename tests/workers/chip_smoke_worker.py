"""Worker: chip_smoke.py's launcher-side phase bodies at transformer.tiny()
on the CPU — the rehearsal of what the smoke runs at real width on the chip.
SMOKE_BODY picks the body; the result goes to SMOKE_OUT.<rank> as JSON."""
import json
import os

import horovod_tpu.jax as hvd
from horovod_tpu.models import transformer as tfm

import chip_smoke

hvd.init()
if os.environ["SMOKE_BODY"] == "train":
    out = chip_smoke.train_phase(tfm.tiny(), batch=2, seq=32, long_batch=1,
                                 long_seq=64, loss_chunk=32)
else:
    out = chip_smoke.ranks_phase(tfm.tiny(), global_batch=8, seq=32)
with open(f"{os.environ['SMOKE_OUT']}.{hvd.rank()}", "w") as f:
    json.dump(out, f)
hvd.shutdown()
