#!/usr/bin/env python3
"""A run of the random-resized-crop cell with the crop broken underneath: the faults
its comparison has to fail, beside ``plant.py``'s. The benchmark's own runs never
load this file.

    python bench/plant_crop.py --fault <name> --workload <cell> --seed <n> --seconds <s>

Faults, planted in the program before the window's checks see it:

- ``shift_box``: each image cropped 8 px to the right of its delivered box (to the
  left where the box touches the right edge).
- ``whole_image``: every box the whole image (the flips kept).
- ``fixed_box``: each image's box drawn from its size alone, so one id gets the same
  box in every epoch.
- ``bf16_crop_decode`` (a control): the crop-resize program's IDCT and its two
  resampling matmuls take bfloat16 operands (one MXU pass, what a matmul at
  default precision does on the TPU), accumulating in float32.

and every fault of ``plant.py`` (``alter_answer``, ``drop_half``, ...).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import plant  # noqa: E402
import run  # noqa: E402

CROP_FAULTS = ("shift_box", "whole_image", "fixed_box", "bf16_crop_decode")


class CropPlant(plant.Plant):
    def before_reader(self):
        if self.fault not in CROP_FAULTS:
            return super().before_reader()
        import numpy as np

        from petastorm_tpu.ops import crop, jpeg

        if self.fault == "bf16_crop_decode":
            import jax.numpy as jnp

            bf16 = jnp.bfloat16

            def idct_bf16(coef_ref, basis_ref, out_ref):
                out_ref[:] = jnp.dot(coef_ref[:].astype(bf16),
                                     basis_ref[:].astype(bf16),
                                     preferred_element_type=jnp.float32) + 128.0

            def resample_bf16(wy, img, wx):
                out = jnp.einsum("nrh,nhwc->nrwc", wy.astype(bf16), img.astype(bf16),
                                 preferred_element_type=jnp.float32)
                return jnp.einsum("nrwc,nsw->nrsc", out.astype(bf16), wx.astype(bf16),
                                  preferred_element_type=jnp.float32)

            jpeg._idct_kernel = idct_bf16
            jpeg._crop_resample = resample_bf16
            return
        if self.fault == "shift_box":
            decode = jpeg.decode_jpeg_crop_batch

            def shifted(planes_list, boxes, size, grayscale=False):
                boxes = np.array(boxes)
                width = np.array([p.width for p in planes_list])
                right = boxes[:, 1] + 8 + boxes[:, 3] <= width
                boxes[:, 1] = np.where(right, boxes[:, 1] + 8,
                                       np.maximum(boxes[:, 1] - 8, 0))
                return decode(planes_list, boxes, size, grayscale)

            jpeg.decode_jpeg_crop_batch = shifted
            return
        draw = crop.draw_boxes

        def planted(sizes, policy, rng):
            boxes = draw(sizes, policy, rng)
            sizes = np.asarray(sizes, np.int64).reshape(-1, 2)
            if self.fault == "whole_image":
                boxes[:, 0:2] = 0
                boxes[:, 2:4] = sizes
                return boxes
            return np.concatenate([
                draw(hw[None], policy, crop.generator(hw[0] << 16 | hw[1], 0))
                for hw in sizes]) if len(sizes) else boxes

        crop.draw_boxes = planted

    def transform(self, batch):
        return batch if self.fault in CROP_FAULTS else super().transform(batch)


def main():
    args = sys.argv[1:]
    if "--fault" not in args:
        raise SystemExit("usage: plant_crop.py --fault <name> --workload ... --seed ...")
    i = args.index("--fault")
    fault = args[i + 1]
    del args[i:i + 2]
    if fault not in CROP_FAULTS and not hasattr(plant.Plant, "_" + fault) \
            and fault not in ("with_replacement", "bf16_stage2"):
        raise SystemExit("unknown fault %r" % fault)
    try:
        run.run(args, plant=CropPlant(fault))
    except run.BenchError as e:
        print("bench: FAILED: %s" % e, file=sys.stderr, flush=True)
        os._exit(2)
    os._exit(0)


if __name__ == "__main__":
    main()
