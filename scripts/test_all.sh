#!/usr/bin/env bash
# Test sweep across virtual mesh sizes — the analog of scripts/test_cpu.sh
# running each test under mpirun -n {1..37}: "multi-node without a cluster"
# is more virtual devices on one host (SURVEY.md §4).
set -u
cd "$(dirname "$0")/.."

MESHES=${MESHES:-"1 2 4 8"}
fails=0

for n in $MESHES; do
  echo "=== mesh size $n: unit tests ==="
  XLA_FLAGS="--xla_force_host_platform_device_count=$n" \
    python -m pytest tests/ -q -x || fails=$((fails+1))
done

echo "=== examples (mesh 8) ==="
for cmd in \
  "examples/mnist_allreduce.py --cpu-mesh 8 --epochs 2" \
  "examples/mnist_parameterserver.py --cpu-mesh 8 --epochs 1 --variant downpour" \
  "examples/mnist_parameterserver.py --cpu-mesh 8 --epochs 1 --variant easgd" \
  "examples/mnist_parameterserver.py --cpu-mesh 8 --epochs 1 --variant easgd --dataparallel" \
  "examples/mnist_parameterserver.py --cpu-mesh 8 --epochs 1 --variant dsgd" \
  "examples/mnist_modelparallel.py --cpu-mesh 8 --epochs 2" \
  "examples/long_context.py --cpu-mesh 8 --seq 128 --steps 10" \
  "examples/long_context.py --cpu-mesh 4 --sp 2 --seq 64 --batch 2 --steps 2 --sp-backend pallas_interpret" \
  "examples/pipeline_stages.py --cpu-mesh 8 --schedule 1f1b" \
  "examples/mnist_sequential.py --cpu --train 2048 --epochs 2" \
  "examples/resnet_allreduce.py --cpu-mesh 8 --model resnet18 --classes 10 --image-size 32 --train 128 --test 32 --per-rank-batch 4 --epochs 1" \
  "examples/blocksequential_2host.py --cpu-mesh 8 --train 512 --epochs 2" \
  ; do
  echo "--- $cmd"
  python $cmd || fails=$((fails+1))
done

echo "=== launcher (mpirun analog): unmodified example, 2 controllers ==="
python -m torchmpi_tpu.launch --nproc 2 --cpu-devices 2 \
  examples/mnist_allreduce.py -- --epochs 1 || fails=$((fails+1))

echo "=== driver entry points ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 python __graft_entry__.py 8 || fails=$((fails+1))

if [ "$fails" -eq 0 ]; then
  echo "Success"   # the reference's rank-0 pass signal
else
  echo "FAILURES: $fails"
  exit 1
fi
