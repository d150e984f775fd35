"""Quickstart: the paper's 4-line usage, TPU-adapted.

PyRadiomics-cuda's promise is that acceleration is *transparent*:

    from radiomics import featureextractor
    ext = featureextractor.RadiomicsFeatureExtractor()
    res = ext.execute('scan.nii.gz', 'mask.nii.gz')
    print(res['MeshVolume'], res['SurfaceArea'])

Here the same four lines run against our JAX/Pallas backend.  The
dispatcher probes for a TPU, uses the Pallas kernels when found, and falls
back to the pure-jnp reference path otherwise -- identical features either
way (set REPRO_BACKEND=interpret to execute the TPU kernel bodies in
Python on CPU).

Run:  PYTHONPATH=src python examples/quickstart.py [scan.nii mask.nii]
"""
import sys

from repro.core.shape_features import ShapeFeatureExtractor
from repro.data.synthetic import make_case
from repro.runtime.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    if len(sys.argv) == 3:  # real NIfTI inputs, as in the paper
        from repro.data.nifti import read_nifti

        image, _ = read_nifti(sys.argv[1])
        mask, spacing = read_nifti(sys.argv[2])
    else:  # synthetic KITS19-like case (offline container)
        image, mask, spacing = make_case((128, 96, 80), seed=7)

    ext = ShapeFeatureExtractor()  # backend='auto': TPU if present, else CPU
    res, times = ext.execute(image, mask, spacing, with_times=True)

    print(f"backend          : {ext.backend}")
    print(f"MeshVolume       : {res['MeshVolume']:.2f}")
    print(f"SurfaceArea      : {res['SurfaceArea']:.2f}")
    print(f"Maximum3DDiameter: {res['Maximum3DDiameter']:.2f}")
    print(f"Sphericity       : {res['Sphericity']:.4f}")
    print(f"mesh vertices    : {int(res['_n_mesh_vertices'])}")
    print(
        "stage times (ms) : "
        f"prep={times.preprocess_ms:.1f} transfer={times.transfer_ms:.1f} "
        f"mc={times.mesh_ms:.1f} diam={times.diameter_ms:.1f}"
    )


if __name__ == "__main__":
    main()
