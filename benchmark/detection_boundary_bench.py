"""Host-op detection boundary timing (VERDICT r4 #7).

A Faster-R-CNN-style training step alternates compiled device segments
with the label-assignment ops this framework deliberately runs
host-side (ops/detection.py:15-19; the reference runs them as CPU-only
kernels INSIDE its graph — detection/rpn_target_assign_op.cc,
generate_proposal_labels_op.cc). This measures the actual cost of that
boundary on the chip:

  phase A (device, one jit): backbone convs -> RPN head ->
          generate_proposals (fixed-shape NMS on device)
  fetch:  proposals + scores to host
  phase B (host): rpn_target_assign + generate_proposal_labels per
          image (numpy)
  phase C (device, one jit): RoI-align + head forward/backward step on
          the sampled rois

One JSON line per phase plus the step total and the host share.

Run: python benchmark/detection_boundary_bench.py  (uses the ambient
device; on the CPU it is a liveness check, not a measurement).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import detection as det

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    B, IM = (8, 512) if on_tpu else (2, 128)
    steps = 20 if on_tpu else 3
    FH = IM // 16                      # C4 feature stride 16
    A = 9                              # anchors per location
    C = 256                            # feature channels
    POST = 512                         # proposals per image

    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.rand(B, IM, IM, 3).astype(np.float32))
    # small conv backbone (4 stride-2 stages to stride 16) + RPN head
    ws = [jnp.asarray(rng.randn(3, 3, cin, cout).astype(np.float32)
                      * (2.0 / (9 * cin)) ** 0.5)
          for cin, cout in ((3, 64), (64, 128), (128, 256), (256, C))]
    w_cls = jnp.asarray(rng.randn(1, 1, C, A).astype(np.float32) * 0.01)
    w_box = jnp.asarray(rng.randn(1, 1, C, 4 * A).astype(np.float32)
                        * 0.01)
    anchors, variances = det.anchor_generator(
        np.zeros((1, C, FH, FH), np.float32),
        anchor_sizes=(32, 64, 128), aspect_ratios=(0.5, 1.0, 2.0),
        stride=(16.0, 16.0))
    im_info = jnp.asarray(
        np.tile(np.array([IM, IM, 1.0], np.float32), (B, 1)))

    def conv(x, w, stride, act=True):
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y) if act else y

    @jax.jit
    def phase_a(imgs):
        h = imgs
        for w in ws:
            h = conv(h, w, 2)
        # NCHW for the proposal op's layout contract; the RPN heads are
        # LINEAR (no activation) — objectness scores and box deltas
        # must span both signs or NMS/top-k see a degenerate
        # tied-at-zero distribution
        feats = jnp.transpose(h, (0, 3, 1, 2))
        cls = jnp.transpose(conv(h, w_cls, 1, act=False), (0, 3, 1, 2))
        box = jnp.transpose(conv(h, w_box, 1, act=False), (0, 3, 1, 2))
        rois, probs, n_valid = det.generate_proposals(
            cls, box, im_info, anchors, variances,
            pre_nms_top_n=2000, post_nms_top_n=POST)
        return feats, cls, box, rois, probs

    # head: RoI-align + 2 fc + cls/box losses, forward+backward
    wh1 = jnp.asarray(rng.randn(C * 7 * 7, 1024).astype(np.float32)
                      * 0.01)
    wh2 = jnp.asarray(rng.randn(1024, 81 + 4 * 81).astype(np.float32)
                      * 0.01)

    def head_loss(params, feats, rois, labels):
        wh1, wh2 = params
        pooled = det.roi_align(feats, rois.reshape(-1, 4),
                               pooled_height=7, pooled_width=7,
                               spatial_scale=1.0 / 16,
                               roi_batch_indices=jnp.repeat(
                                   jnp.arange(B), rois.shape[1]))
        flat = pooled.reshape(pooled.shape[0], -1)
        h = jax.nn.relu(flat @ wh1)
        out = h @ wh2
        logits = out[:, :81]
        onehot = jax.nn.one_hot(labels.reshape(-1), 81)
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * onehot, -1))

    @jax.jit
    def phase_c(params, feats, rois, labels):
        loss, grads = jax.value_and_grad(head_loss)(params, feats, rois,
                                                    labels)
        return loss, grads

    gt_boxes = [np.sort(rng.rand(12, 2, 2) * IM, axis=1)
                .transpose(0, 2, 1).reshape(12, 4).astype(np.float32)
                for _ in range(B)]
    gt_classes = [rng.randint(1, 81, 12).astype(np.int32)
                  for _ in range(B)]

    anchors_np = np.asarray(anchors).reshape(-1, 4)
    variances_np = np.asarray(variances).reshape(-1, 4)
    host_split = [0.0, 0.0]    # [rpn_target_assign, proposal_labels]

    def host_phase(rois_np, cls_np, box_np):
        """The boundary under test: per-image numpy assigners.
        rpn_target_assign depends only on anchors+gt (input-pipeline-
        movable); generate_proposal_labels consumes the CURRENT step's
        proposals (must interleave)."""
        all_rois, all_labels = [], []
        for i in range(B):
            ta = time.perf_counter()
            det.rpn_target_assign(
                box_np[i].reshape(-1, 4),
                cls_np[i].reshape(-1, 1),
                anchors_np, variances_np,
                gt_boxes[i], None, [IM, IM, 1.0])
            tb = time.perf_counter()
            rois, labels, *_ = det.generate_proposal_labels(
                rois_np[i], gt_classes[i], None, gt_boxes[i],
                [IM, IM, 1.0], batch_size_per_im=POST)
            tc = time.perf_counter()
            host_split[0] += tb - ta
            host_split[1] += tc - tb
            pad = POST - rois.shape[0]
            all_rois.append(np.pad(rois, ((0, pad), (0, 0))))
            all_labels.append(np.pad(labels.reshape(-1), (0, pad)))
        return (np.stack(all_rois).astype(np.float32),
                np.stack(all_labels).astype(np.int32))

    params = (wh1, wh2)
    t_a = t_fetch = t_host = t_c = 0.0
    host_split[0] = host_split[1] = 0.0
    # warmup compiles
    feats, cls, box, rois, probs = phase_a(imgs)
    rois_np = np.asarray(rois)
    s_rois, s_labels = host_phase(rois_np, np.asarray(cls),
                                  np.asarray(box))
    loss, _ = phase_c(params, feats, jnp.asarray(s_rois),
                      jnp.asarray(s_labels))
    float(np.asarray(loss))

    host_split[0] = host_split[1] = 0.0
    for _ in range(steps):
        t0 = time.perf_counter()
        feats, cls, box, rois, probs = phase_a(imgs)
        jax.block_until_ready(rois)
        t1 = time.perf_counter()
        rois_np = np.asarray(rois)
        cls_np = np.asarray(cls)
        box_np = np.asarray(box)
        t2 = time.perf_counter()
        s_rois, s_labels = host_phase(rois_np, cls_np, box_np)
        t3 = time.perf_counter()
        loss, grads = phase_c(params, feats, jnp.asarray(s_rois),
                              jnp.asarray(s_labels))
        float(np.asarray(loss))
        t4 = time.perf_counter()
        t_a += t1 - t0
        t_fetch += t2 - t1
        t_host += t3 - t2
        t_c += t4 - t3

    ms = [round(t / steps * 1e3, 2) for t in (t_a, t_fetch, t_host, t_c)]
    total = round(sum(ms), 2)
    print(json.dumps({
        "metric": "detection_step_phase_ms",
        "device_backbone_rpn_proposals": ms[0],
        "fetch_to_host": ms[1],
        "host_assigners": ms[2],
        "host_rpn_target_assign": round(
            host_split[0] / steps * 1e3, 2),
        "host_proposal_labels": round(
            host_split[1] / steps * 1e3, 2),
        "device_head_fwd_bwd": ms[3],
        "total_ms": total,
        "host_share_pct": round(100 * ms[2] / total, 1),
        "batch": B, "image": IM, "device": dev.platform,
    }))


if __name__ == "__main__":
    main()
