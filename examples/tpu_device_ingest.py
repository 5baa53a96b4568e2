"""Device-resident ingest via the tpu:// URI scheme.

Demonstrates the north-star path end to end (BASELINE.json: "Stream/
SeekStream gain a tpu:// URI that DMAs RecordIO chunks straight to
device"):

1. write a RecordIO dataset (records containing aligned magic bytes, so
   the escape framing is exercised),
2. stream it into device memory as raw chunks (TPUSeekStream.device_chunks:
   async transfers with a lookahead window),
3. ingest it sharded as record batches straight to the device
   (recordio_device_batches: zero host-side record copy with the native
   engine), and reduce over the payload on device.

Runs on whatever JAX finds: on a TPU host the batches land in HBM; under
``JAX_PLATFORMS=cpu`` it runs on an 8-virtual-device CPU mesh.
"""

import os
import struct

# virtual devices for a CPU run (read at backend init; no effect on TPU)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_tpu.io import RECORDIO_MAGIC, RecordIOWriter, create_stream
from dmlc_tpu.io.stream import create_seek_stream_for_read
from dmlc_tpu.io.tpu_fs import recordio_device_batches


def main() -> None:
    path = "/tmp/dmlc_tpu_example.rec"
    rng = np.random.RandomState(0)
    magic = struct.pack("<I", RECORDIO_MAGIC)
    records = []
    with open(path, "wb") as fh:
        w = RecordIOWriter(fh)
        for i in range(500):
            rec = (magic * 2 + rng.bytes(rng.randint(10, 400))
                   if i % 9 == 0 else rng.bytes(rng.randint(1, 2000)))
            records.append(rec)
            w.write_record(rec)
    print(f"wrote {len(records)} records "
          f"({os.path.getsize(path) / 1e6:.1f} MB, "
          f"{w.escaped_magic_count} escaped magics)")

    # --- raw device chunks through the tpu:// stream
    s = create_seek_stream_for_read(f"tpu://{path}")
    total = 0
    nchunks = 0
    for chunk in s.device_chunks(chunk_bytes=256 * 1024, lookahead=2):
        chunk = jax.block_until_ready(chunk)
        total += chunk.size
        nchunks += 1
    s.close()
    print(f"device_chunks: {nchunks} chunks, {total} bytes on "
          f"{jax.devices()[0].platform}")

    # --- sharded record batches straight to device + on-device reduce
    ndev = min(4, len(jax.devices()))
    checksum = 0  # host-side accumulation: per-part sums live on
    nrec = 0      # DIFFERENT devices and must not be added under jit
    for part in range(ndev):
        dev = jax.devices()[part]
        for batch in recordio_device_batches(f"tpu://{path}", part, ndev,
                                             device=dev):
            payload, starts, ends = (batch["payload"], batch["starts"],
                                     batch["ends"])
            nrec += int(starts.shape[0])
            # on-device reduction over the RECORD bytes only: the
            # payload buffer is the raw chunk, so frame headers sit
            # between record spans — mask them out with a +1/-1
            # scatter + cumsum coverage (spans never overlap)
            n = payload.shape[0]
            delta = (jnp.zeros(n + 1, jnp.int32)
                     .at[starts].add(1).at[ends].add(-1))
            covered = jnp.cumsum(delta[:-1]) > 0
            part_sum = jnp.sum(jnp.where(covered,
                                         payload.astype(jnp.uint32), 0))
            checksum = (checksum + int(part_sum)) % (1 << 32)
    expect = sum(sum(r) for r in records) % (1 << 32)
    got = checksum
    assert got == expect, (got, expect)
    assert nrec == len(records)
    print(f"recordio_device_batches: {nrec} records across {ndev} "
          f"device shards, on-device checksum OK ({got})")


if __name__ == "__main__":
    main()
