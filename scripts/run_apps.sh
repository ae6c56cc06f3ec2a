#!/usr/bin/env bash
# App smoke runs on toy data (reference tests/run_apps.sh: MF dsgd +
# columnwise, KGE, word2vec): a functional check at toy sizes, pinned to
# the CPU below. It says nothing about the chip — chip_smoke.py does.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

FAST="--sys.sync.max_per_sec 0"

echo "=== simple ==="
python -m adapm_tpu.apps.simple --iterations 5 $FAST

echo "=== matrix_factorization (dsgd) ==="
python -m adapm_tpu.apps.matrix_factorization --rows 48 --cols 32 \
  --nnz 600 --rank 4 --epochs 2 --batch_size 16 --lr 0.1 \
  --algorithm dsgd $FAST

echo "=== matrix_factorization (columnwise) ==="
python -m adapm_tpu.apps.matrix_factorization --rows 48 --cols 32 \
  --nnz 600 --rank 4 --epochs 2 --batch_size 16 --lr 0.1 \
  --algorithm columnwise $FAST

echo "=== word2vec ==="
python -m adapm_tpu.apps.word2vec --synthetic_vocab 60 \
  --synthetic_sentences 80 --dim 8 --window 3 --negative 3 \
  --epochs 2 --batch_size 128 --readahead 20 $FAST

echo "=== knowledge_graph_embeddings (complex) ==="
python -m adapm_tpu.apps.knowledge_graph_embeddings --dim 8 \
  --neg_ratio 2 --synthetic_entities 60 --synthetic_relations 4 \
  --synthetic_triples 400 --epochs 2 --batch_size 32 --eval_every 2 \
  --eval_triples 40 $FAST

echo "=== knowledge_graph_embeddings, 2 launched processes ==="
# the reference smoke-runs every app under `dmlc_local.py -s 2`
# (tests/run_apps.sh); same shape here via the launcher
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
python -m adapm_tpu.launcher -n 2 --no-keepalive -- \
  python -m adapm_tpu.apps.knowledge_graph_embeddings --dim 8 \
  --neg_ratio 2 --synthetic_entities 60 --synthetic_relations 4 \
  --synthetic_triples 400 --epochs 2 --batch_size 32 --eval_every 2 \
  --eval_triples 40 $FAST

echo "=== bindings apps (CTR + GCN, adapm-pytorch-apps workload shapes) ==="
PYTHONPATH=. python examples/ctr_example.py
PYTHONPATH=. python examples/gcn_example.py

echo "ALL APPS PASSED"
