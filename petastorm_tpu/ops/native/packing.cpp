// Sequence packing on the host: first-fit placement of document chunks into the
// rows of one packed batch, with the chunk's tokens copied into their slots.
//
// Built as its own shared object (see __init__.py); called through ctypes, which
// releases the GIL for the whole call.
#include <cstdint>
#include <cstring>

extern "C" {

// Places chunks [i, n) in order into one batch of `rows` rows of `length` slots.
// Chunk k is values[src[k] .. src[k] + len[k]) with 1 <= len[k] <= length. Each goes
// to the lowest-index row whose used slots fill[r] leave room for it; its tokens are
// copied to tokens[r * length + fill[r] ...] and (r, fill[r], len[k]) is appended to
// table (int32 triples) at *count, which is advanced. Returns the index of the first
// chunk that fits in no row (the batch is full), or n when every chunk was placed.
int64_t ptpu_pack_place(const int32_t* values, const int64_t* src, const int64_t* len,
                        int64_t i, int64_t n, int32_t rows, int32_t length,
                        int32_t* fill, int32_t* tokens, int32_t* table, int64_t* count) {
    int64_t k = *count;
    for (; i < n; ++i) {
        const int64_t c = len[i];
        int32_t r = 0;
        while (r < rows && fill[r] + c > length) ++r;
        if (r == rows) break;
        std::memcpy(tokens + static_cast<int64_t>(r) * length + fill[r], values + src[i],
                    static_cast<size_t>(c) * sizeof(int32_t));
        table[3 * k] = r;
        table[3 * k + 1] = fill[r];
        table[3 * k + 2] = static_cast<int32_t>(c);
        ++k;
        fill[r] += static_cast<int32_t>(c);
    }
    *count = k;
    return i;
}

}  // extern "C"
