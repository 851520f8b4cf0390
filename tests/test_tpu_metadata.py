"""GKE/GCE TPU pod metadata: slice self-labeling without hand-set env.

Reference parity: `python/ray/_private/accelerators/tpu.py:326-433` —
pod type / worker id / slice name / topology come from the GCE metadata
server (GKE presets env vars instead). Each simulated node points
`RAY_TPU_GCE_METADATA_ENDPOINT` at its own path of a local mock server,
exactly like each TPU VM sees its own per-VM metadata; NO pod-type /
worker-id / slice-name env vars are set anywhere.
"""

import http.server
import threading

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import remove_placement_group
from ray_tpu.util.accelerators import reserve_tpu_slice

SLICE = "metadata-slice-7"


class _MetaHandler(http.server.BaseHTTPRequestHandler):
    """`/node<K>/<key>` → that simulated VM's metadata attribute."""

    VALUES = {
        "accelerator-type": "v5e-8",
        "instance-id": SLICE,
        "tpu-env": "ACCELERATOR_TYPE: 'v5e-8'\nTOPOLOGY: '2x4'\n",
    }

    def do_GET(self):
        parts = self.path.strip("/").split("/")
        if len(parts) != 2 or not parts[0].startswith("node") \
                or self.headers.get("Metadata-Flavor") != "Google":
            self.send_response(404)
            self.end_headers()
            return
        node, key = parts
        if key == "agent-worker-number":
            value = node[len("node"):]
        else:
            value = self.VALUES.get(key)
        if value is None:
            self.send_response(404)
            self.end_headers()
            return
        body = value.encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # keep pytest output clean
        pass


@pytest.fixture(scope="module")
def metadata_server():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _MetaHandler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()


@pytest.fixture(scope="module")
def cluster(metadata_server):
    c = Cluster(num_cpus=0)
    # two hosts of a fake v5e-8 slice: chip COUNT from the (mocked) /dev
    # scan equivalent; everything else self-labels from metadata
    # scrub any ambient TPU identity env (a TPU VM image presets
    # TPU_ACCELERATOR_TYPE etc.) — empty string means "unset"
    scrub = {k: "" for k in ("TPU_ACCELERATOR_TYPE", "TPU_NAME",
                             "TPU_WORKER_ID", "TPU_TOPOLOGY",
                             "RAY_TPU_POD_TYPE", "RAY_TPU_SLICE_NAME",
                             "RAY_TPU_WORKER_ID")}
    for k in range(2):
        c.add_node(num_cpus=2, num_tpu_chips=4, env={
            **scrub,
            "RAY_TPU_GCE_METADATA_ENDPOINT": f"{metadata_server}/node{k}/",
        })
    c.connect()
    c.wait_for_nodes(3)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_nodes_self_label_from_metadata(cluster):
    tpu_nodes = [n for n in ray_tpu.nodes()
                 if n["labels"].get("ray.io/tpu-slice-name")]
    assert len(tpu_nodes) == 2
    for n in tpu_nodes:
        assert n["labels"]["ray.io/tpu-slice-name"] == SLICE
        assert n["labels"]["ray.io/tpu-pod-type"] == "v5e-8"
        assert n["labels"]["ray.io/tpu-topology"] == "2x4"
    ids = sorted(n["labels"]["ray.io/tpu-worker-id"] for n in tpu_nodes)
    assert ids == ["0", "1"]
    # only worker 0 advertises the slice-head gang anchor
    assert ray_tpu.cluster_resources().get("TPU-v5e-8-head") == 1.0


def test_gang_placement_with_only_metadata(cluster):
    res = reserve_tpu_slice("v5e-8")
    assert res.slice_name == SLICE

    @ray_tpu.remote
    class Pin:
        def ids(self):
            from ray_tpu.core.resources import tpu_slice_name, tpu_worker_id

            return (tpu_slice_name(),
                    ray_tpu.get_runtime_context().node_id.hex())

    actors = [
        Pin.options(num_cpus=0, resources={"TPU": 4},
                    label_selector=res.label_selector).remote()
        for _ in range(2)
    ]
    out = ray_tpu.get([a.ids.remote() for a in actors], timeout=60)
    assert all(name == SLICE for name, _ in out)
    assert out[0][1] != out[1][1]  # one host each
    for a in actors:
        ray_tpu.kill(a)
    remove_placement_group(res.pg)
