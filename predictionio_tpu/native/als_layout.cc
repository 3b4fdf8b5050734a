// The order stage of the ALS layout (models/als.py als_prepare,
// _bucket_side step 1) as a stable counting sort: count, then scatter.
//
// The interactions of a side are ordered by their entity's permuted
// position. The counts per entity are known before the scatter
// (als_count, or the caller's bincount), so `cursor` arrives holding
// every position's first output slot and one sequential walk over the
// COO places each interaction: the walk is in COO order, so within an
// entity the interactions keep their order of appearance (the sort is
// stable by construction), and no permutation is ever materialised.
//
// Plain C ABI, no dependency; the caller (Python, ctypes) validates
// dtypes and contiguity and owns every buffer.

#include <cstdint>

extern "C" {

// counts[idx[i]]++ over one column of ids, `counts` arriving zeroed:
// np.bincount without its int64 copy of the column. Returns -1, or the
// first row whose id is outside [0, n).
int64_t als_count(int64_t nnz, const int32_t* idx, int64_t n,
                  int64_t* counts) {
  for (int64_t i = 0; i < nnz; ++i) {
    const int64_t e = idx[i];
    if (e < 0 || e >= n) return i;
    ++counts[e];
  }
  return -1;
}

// o[s] = other_pos[idx_other[i]], v[s] = vals[i] with
// s = cursor[inv_perm[idx_self[i]]]++, for i in COO order.
// Returns -1, or the first row i whose ids or output slot fall outside
// the arrays (nothing is written for that row; `cursor` is left where
// the walk stopped).
int64_t als_order_scatter(
    int64_t nnz, const int32_t* idx_self, const int32_t* idx_other,
    const int32_t* other_pos, int64_t n_other_pos, const float* vals,
    const int32_t* inv_perm, int64_t n_self, int64_t* cursor,
    int32_t* o, float* v) {
  for (int64_t i = 0; i < nnz; ++i) {
    const int64_t e = idx_self[i], j = idx_other[i];
    if (e < 0 || e >= n_self || j < 0 || j >= n_other_pos) return i;
    const int64_t p = inv_perm[e];
    if (p < 0 || p >= n_self) return i;
    const int64_t s = cursor[p];
    if (s < 0 || s >= nnz) return i;
    cursor[p] = s + 1;
    o[s] = other_pos[j];
    v[s] = vals[i];
  }
  return -1;
}

}  // extern "C"
