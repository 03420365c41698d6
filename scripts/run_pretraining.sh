#!/bin/bash
# Launch pretraining. Under SPMD the reference's entire launcher layer
# (torch.distributed.launch per node, Cobalt SSH fan-out, SLURM mpirun —
# SURVEY §5.8) collapses to one python process per TPU-VM host; the TPU
# runtime provides the rendezvous. For multi-host DCN clusters pass the
# coordinator explicitly (bert_pytorch_tpu.parallel.dist.initialize).
#
#   scripts/run_pretraining.sh configs/bert_pretraining_phase1_config.json \
#       <dir of encoded .hdf5 shards> <output dir>
set -euo pipefail
CONFIG=${1:?run config json}
INPUT=${2:?input dir with .hdf5 shards}
OUTPUT=${3:?output dir}
shift 3
exec python run_pretraining.py --config_file "$CONFIG" \
    --input_dir "$INPUT" --output_dir "$OUTPUT" "$@"
