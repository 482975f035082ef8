"""The reference's Ward clustering numbers its clusters by their
smallest member, as the program's does, also where the Eq. 9 matrix it
is given is not exactly symmetric (on the TPU the Gram's two halves
round apart, and arccos widens that near parallel rows): the two-stage
sampler draws one Gumbel per cluster number, so a numbering that
differs changes the selection while the partition is the same."""
import numpy as np
import pytest

from benchlib import reference

#: 4 clients; the smallest entry, (0, 2), is moved below the diagonal
#: by a hair, either way, in the test
BASE = np.array([[0.0, 0.9, 0.1, 0.8],
                 [0.9, 0.0, 0.7, 0.6],
                 [0.1, 0.7, 0.0, 0.5],
                 [0.8, 0.6, 0.5, 0.0]], np.float32)


@pytest.mark.parametrize("below", [0.0, -1e-6, 1e-6])
def test_clusters_numbered_by_smallest_member(below):
    d = BASE.copy()
    d[2, 0] += below
    labels = np.asarray(reference.ward_labels(d, 3))
    np.testing.assert_array_equal(labels, [0, 1, 0, 2])

