"""How testdata/synthetic_tpu.xplane.pb was made (run once by hand; needs
tensorflow's xplane_pb2, which the benchmark itself never imports).

Two device planes shaped like a TPU's: a Steps line, an XLA Modules line
and an XLA Ops line that all cover the same time, the op line nested (a
``while`` spans its body, which holds one asynchronous collective as a
start/done pair of fusions round a kernel), plus a host plane: a thread
that outlasts the device work, and the capture's own ``benchmark_capture``
event, 0.5-9.75 ms, which is the traced window. Device 0 is busy 6 ms on
its op line inside that window; a sum over its lines would read 22 ms.
Device 1 runs 1 ms later, so its last op is cut by the window's end.
"""
from tensorflow.tsl.profiler.protobuf import xplane_pb2

MS = 1_000_000  # ns


def plane(space, name, lines):
    p = space.planes.add()
    p.name = name
    ids = {}
    for li, (line_name, events) in enumerate(lines):
        line = p.lines.add()
        line.id, line.name, line.timestamp_ns = li, line_name, 0
        for ev_name, start_ms, dur_ms in events:
            if ev_name not in ids:
                ids[ev_name] = len(ids) + 1
                p.event_metadata[ids[ev_name]].id = ids[ev_name]
                p.event_metadata[ids[ev_name]].name = ev_name
            e = line.events.add()
            e.metadata_id = ids[ev_name]
            e.offset_ps = int(start_ms * MS * 1000)
            e.duration_ps = int(dur_ms * MS * 1000)


START = ("%async-collective-start.7 = (bf16[1,1024,8]{2,1,0}, bf16[1,4096,8]{2,1,0}) "
         "fusion(bf16[1,1024,8]{2,1,0} %p.1), kind=kCustom, calls=%fused_computation.9")
DONE = ("%async-collective-done.7 = bf16[1,4096,8]{2,1,0} fusion(bf16[1,1024,8]{2,1,0} "
        "%get-tuple-element.3), kind=kCustom, calls=%fused_computation.10")
GATHER = ("%all-gather.3 = bf16[8]{0} all-gather(bf16[2]{0} %fusion.1), channel_id=1, "
          "replica_groups=[1,4]<=[4], dimensions={0}")
space = xplane_pb2.XSpace()
for dev, shift in ((0, 0.0), (1, 1.0)):
    plane(space, f"/device:TPU:{dev}", [
        ("Steps", [("step 0", 1 + shift, 8)]),
        ("XLA Modules", [("jit_train_step(123)", 1 + shift, 5),
                         ("jit_train_step(123)", 7 + shift, 2)]),
        ("XLA Ops", [("while.1", 1 + shift, 4),
                     ("fusion.1", 1 + shift, 1.4), (START, 2.4 + shift, 0.1),
                     ("custom-call.2", 2.5 + shift, 1.9), (DONE, 4.4 + shift, 0.1),
                     (GATHER, 5 + shift, 0.5),
                     ("fusion.4", 7.5 + shift, 1.5)]),
    ])
plane(space, "/host:CPU", [
    ("python", [("$train.py:120 timed_step", 0, 10), ("$session.py:88 report", 5.6, 1.8)]),
    ("main/1", [("benchmark_capture", 0.5, 9.25)]),
])
with open(__file__.rsplit("/", 1)[0] + "/synthetic_tpu.xplane.pb", "wb") as f:
    f.write(space.SerializeToString())
